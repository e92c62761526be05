"""Serve a (reduced) model with batched one-token decode — prefill, then
cached generation; prints tokens/step timing.

    PYTHONPATH=src python examples/serve_lm.py [arch] [new_tokens]
"""
import sys
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.inputs import concrete_batch
from repro.models import init_params, model_params_def
from repro.models import transformer as T
from repro.serving import build_serve_step

enable_compile_cache()
arch = sys.argv[1] if len(sys.argv) > 1 else "gemma3-4b"
new_tokens = int(sys.argv[2]) if len(sys.argv) > 2 else 32
B, PROMPT = 4, 16

cfg = get_config(arch, smoke=True)
params = init_params(model_params_def(cfg), jax.random.PRNGKey(0), jnp.float32)
batch = concrete_batch(cfg, B, PROMPT)
batch.pop("patch_embeds", None)

# prefill: teacher-forced through the cache (also validates cache math)
enc_out = None
if cfg.is_encoder_decoder:
    enc_out = T._encode(params, batch["frames"], cfg, None)
cache = T.init_cache(cfg, B, PROMPT + new_tokens, jnp.float32,
                     enc_len=enc_out.shape[1] if enc_out is not None else 0)
serve_step = jax.jit(build_serve_step(cfg), donate_argnums=(1,))

tok = batch["tokens"][:, :1]
times = []
out_tokens = []
for t in range(PROMPT + new_tokens - 1):
    db = {"tokens": tok, "step": jnp.asarray(t, jnp.int32)}
    if cfg.rope_kind == "mrope":
        db["positions"] = jnp.full((B, 3, 1), t, jnp.int32)
    if cfg.is_encoder_decoder:
        db["enc_out"] = enc_out
    t0 = time.perf_counter()
    nxt, cache = serve_step(params, cache, db)
    nxt.block_until_ready()
    times.append(time.perf_counter() - t0)
    if t + 1 < PROMPT:
        tok = batch["tokens"][:, t + 1:t + 2]   # teacher-forced prompt
    else:
        tok = nxt[:, None]                       # free-running generation
        out_tokens.append(int(nxt[0]))

print(f"arch={arch} generated {len(out_tokens)} tokens/seq, batch={B}")
print("first sequence:", out_tokens[:16])
steady = times[2:]
print(f"decode step: {1e3 * sum(steady)/len(steady):.2f} ms "
      f"({B/ (sum(steady)/len(steady)):.1f} tok/s batch throughput)")
