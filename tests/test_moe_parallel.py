"""shard_map expert-parallel MoE == scatter MoE (8-device subprocess)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import warnings; warnings.filterwarnings("ignore")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs import get_config
    from repro.models import init_params
    from repro.models import moe as M

    cfg = get_config("deepseek-v3-671b", smoke=True).replace(
        capacity_factor=8.0, n_experts=8)
    params = init_params(M.experts_def(cfg), jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 16, cfg.d_model)), jnp.float32) * 0.3
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    with jax.sharding.set_mesh(mesh):
        y1, _ = jax.jit(lambda p, x: M._moe_scatter(p, x, cfg))(params, x)
        cfg2 = cfg.replace(moe_impl="shard_map")
        y2, _ = jax.jit(lambda p, x: M.moe_apply(p, x, cfg2))(params, x)
        # grads must flow through the shard_map path
        g = jax.jit(jax.grad(lambda p: jnp.sum(M.moe_apply(p, x, cfg2)[0]**2)
                             ))(params)
    err = float(jnp.abs(y1 - y2).max())
    assert err < 1e-5, err
    gsum = sum(float(jnp.abs(l).sum()) for l in jax.tree.leaves(g))
    assert gsum > 0
    print("OK", err)
""")


def test_shard_map_moe_matches_scatter():
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, cwd=os.getcwd(),
                         timeout=580)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
