"""Train a (reduced) assigned-architecture LM for a few hundred steps with
checkpoint/restart — the end-to-end training driver.

    PYTHONPATH=src python examples/train_lm.py [arch] [steps]
"""
import sys
import tempfile

from repro.compile_cache import enable_compile_cache
from repro.launch.train import train

enable_compile_cache()

arch = sys.argv[1] if len(sys.argv) > 1 else "granite-8b"
steps = int(sys.argv[2]) if len(sys.argv) > 2 else 200

with tempfile.TemporaryDirectory() as ckpt:
    print(f"== training {arch} (reduced config) for {steps} steps ==")
    _, final_loss = train(arch, smoke=True, steps=steps, batch=8, seq=64,
                          lr=3e-3, ckpt_dir=ckpt, ckpt_every=50,
                          n_microbatches=2)
    print(f"final loss {final_loss:.4f}")
    # restart from the checkpoint and keep training (resume path)
    _, resumed_loss = train(arch, smoke=True, steps=steps + 20, batch=8,
                            seq=64, lr=3e-3, ckpt_dir=ckpt, ckpt_every=50,
                            n_microbatches=2)
    print(f"after resume +20 steps: loss {resumed_loss:.4f}")
