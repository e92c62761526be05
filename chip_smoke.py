"""Chip smoke: alpha-seeded 10-fold CV at adult's published size on one TPU.

    python chip_smoke.py

Runs the system's main path once, through the entry points a user calls,
on the paper's adult deployment at its published shape (32,561 x 123,
C = 100, gamma = 0.5, k = 10; data generated from seed 0):

  (a) kernels   compiled ``fused_smo_step`` (blocks derived from shape and
                VMEM) and ``rbf_kernel_matrix`` against their jnp oracles;
  (b) dense CV  ``run_cv`` with cold, sir, mir and ato: every fold
                converges, and every seeded fold classifies its held-out
                chunk exactly as cold does (the paper's "same results");
  (c) matrix-free CV  ``run_cv_batched(source_backend="pallas_rbf")``:
                every fold converges, fold accuracies equal dense cold's,
                and fold objectives agree within ``OBJ_RTOL``.

Progress goes to stdout line by line; the last line is one JSON object
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Without a TPU, or outside a checkout of this repository, it exits
nonzero and prints no result.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

N_ADULT = 32561          # adult's published cardinality (paper Table 1)
K_FOLDS = 10
METHODS = ("cold", "sir", "mir", "ato")
#: dual objectives of two solves of one fold, each to gap <= tol = 1e-3,
#: agree to this relative bound (DESIGN.md §Precision policy)
OBJ_RTOL = 1e-4
#: kernel-vs-oracle bounds: both sides are f32 kernel values at full f32
#: matmul precision; only the contraction order differs
KERNEL_ATOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform!r}")
    return dev


def assert_compiled() -> None:
    """Every Pallas launch of this run must be a compiled Mosaic kernel:
    the sources built by the CV entry points resolve ``interpret=None``
    here."""
    from repro.kernels.rbf import auto_interpret
    assert auto_interpret(None) is False, "Pallas would run interpreted"


def phase_kernels(X) -> dict:
    """Compiled kernels at the full shape against their jnp oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ref import fused_smo_step_ref, rbf_kernel_matrix_ref
    from repro.kernels.rbf import rbf_kernel_matrix
    from repro.kernels.smo_step import compiled_blocks, fused_smo_step

    n, d = X.shape
    bm, bk = compiled_blocks(n, d)
    sq = jnp.sum(X * X, axis=1)
    xij = X[jnp.asarray([3, n - 1])]
    f = jnp.asarray(np.random.default_rng(0).normal(size=n))   # f64 state
    delta = 0.37
    lowered = fused_smo_step.lower(f, X, xij, sq, delta, gamma=0.5,
                                   interpret=False)
    assert "tpu_custom_call" in lowered.as_text(), "no Mosaic kernel"
    out = jax.block_until_ready(
        fused_smo_step(f, X, xij, sq, delta, gamma=0.5, interpret=False))
    ref = fused_smo_step_ref(f, X, xij, sq, delta, 0.5)
    step_err = float(jnp.max(jnp.abs(out - ref)))
    assert out.dtype == jnp.float64 and step_err <= KERNEL_ATOL * delta, \
        step_err
    rows = X[:4096]
    K = jax.block_until_ready(rbf_kernel_matrix(rows, X, 0.5,
                                                interpret=False))
    Kr = rbf_kernel_matrix_ref(rows, X, 0.5)
    rbf_err = float(jnp.max(jnp.abs(K - Kr)))
    assert K.dtype == jnp.float32 and rbf_err <= KERNEL_ATOL, rbf_err
    return {"fused_smo_step_blocks": [bm, bk],
            "fused_smo_step_max_abs_err": step_err,
            "rbf_slab_max_abs_err": rbf_err}


def in_use() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB in use"


def phase_dense(ds, k: int) -> dict:
    from repro.core.cv import run_cv
    reports = {}
    for method in METHODS:
        t0 = time.perf_counter()
        rep = run_cv(ds, k=k, method=method)
        wall = time.perf_counter() - t0
        reports[method] = rep
        log(f"  {method:5s} wall {wall:8.2f} s  kernel {rep.kernel_time:.3f}"
            f" s  init {rep.total_init_time:.3f} s  solve "
            f"{rep.total_solve_time:.3f} s  iterations "
            f"{rep.total_iterations}  per fold "
            f"{[f.n_iter for f in rep.folds]}  accuracy {rep.accuracy}  "
            f"{in_use()}")
        assert all(f.converged for f in rep.folds), f"{method}: unconverged"
    cold = [f.acc_correct for f in reports["cold"].folds]
    for method in METHODS[1:]:
        got = [f.acc_correct for f in reports[method].folds]
        assert got == cold, f"{method} accuracies {got} != cold {cold}"
    return reports


def phase_matrix_free(ds, k: int, cold) -> object:
    from repro.core.cv import run_cv_batched
    t0 = time.perf_counter()
    rep = run_cv_batched(ds, k=k, source_backend="pallas_rbf")
    wall = time.perf_counter() - t0
    log(f"  {rep.method} wall {wall:8.2f} s  iterations "
        f"{rep.total_iterations}  per fold {[f.n_iter for f in rep.folds]}"
        f"  accuracy {rep.accuracy}  occupancy {rep.occupancy}")
    assert all(f.converged for f in rep.folds), "cold_pallas: unconverged"
    for fp, fd in zip(rep.folds, cold.folds):
        assert fp.acc_correct == fd.acc_correct, (fp.fold, fp.acc_correct,
                                                  fd.acc_correct)
        rel = abs(fp.objective - fd.objective) / abs(fd.objective)
        assert rel <= OBJ_RTOL, (fp.fold, fp.objective, fd.objective)
    return rep


def main() -> int:
    root = pathlib.Path(__file__).resolve().parent
    if not (root / "src" / "repro" / "compile_cache.py").is_file():
        print("chip_smoke: run from a checkout of this repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(root / "src"))
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    dev = require_tpu()
    from repro.data.svm_suite import make_dataset
    from repro.svm import cost_model
    from repro.svm.precision import kernel_input

    log(f"device {dev.platform} {dev.device_kind} x{len(jax.devices())}"
        f"  jax {jax.__version__}  compile cache {cache}")
    assert_compiled()
    log("pool verdicts (cost model; no tpu entry -> fallback): max_width "
        f"dense={cost_model.pick_max_width(kinds=('dense',))} pallas_rbf="
        f"{cost_model.pick_max_width(kinds=('pallas_rbf',))}  shrink "
        f"dense={cost_model.pick_shrink(kinds=('dense',))} pallas_rbf="
        f"{cost_model.pick_shrink(kinds=('pallas_rbf',))}")

    t0 = time.perf_counter()
    ds = make_dataset("adult", n_override=N_ADULT)
    n = (ds.n // K_FOLDS) * K_FOLDS
    log(f"adult {ds.X.shape} C={ds.C} gamma={ds.gamma} k={K_FOLDS} "
        f"(solved n={n})  data {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    info = phase_kernels(kernel_input(ds.X[:n]))
    log(f"phase a (kernels) {time.perf_counter() - t0:.2f} s  {info}")

    t0 = time.perf_counter()
    dense = phase_dense(ds, K_FOLDS)
    log(f"phase b (dense CV) {time.perf_counter() - t0:.2f} s")

    t0 = time.perf_counter()
    phase_matrix_free(ds, K_FOLDS, dense["cold"])
    log(f"phase c (matrix-free CV) {time.perf_counter() - t0:.2f} s")

    stats = dev.memory_stats() or {}
    log(f"peak device memory {stats.get('peak_bytes_in_use')} bytes")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
