"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode executes the kernel bodies on CPU)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import (flash_attention, fused_smo_step,
                               rbf_kernel_matrix)
from repro.kernels.ref import (flash_attention_ref, fused_smo_step_ref,
                               rbf_kernel_matrix_ref)

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("n,m,d", [(64, 64, 16), (100, 130, 70), (257, 63, 9),
                                   (32, 512, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_rbf_shapes_dtypes(n, m, d, dtype):
    X = jnp.asarray(RNG.normal(size=(n, d)), dtype)
    Z = jnp.asarray(RNG.normal(size=(m, d)), dtype)
    K = rbf_kernel_matrix(X, Z, 0.37, bm=64, bn=64, bk=64)
    Kr = rbf_kernel_matrix_ref(X, Z, 0.37)
    tol = 1e-5 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(np.asarray(K), np.asarray(Kr), atol=tol)


def test_rbf_block_shape_independence():
    # f64: accumulation-order differences across block shapes stay below
    # 1e-12; f32 ordering effects are a separate (dtype-sweep) test
    X = jnp.asarray(RNG.normal(size=(120, 40)), jnp.float64)
    ref = rbf_kernel_matrix_ref(X, X, 0.5)
    for bm, bn, bk in [(32, 32, 16), (64, 128, 32), (128, 64, 64)]:
        K = rbf_kernel_matrix(X, X, 0.5, bm=bm, bn=bn, bk=bk)
        np.testing.assert_allclose(np.asarray(K), np.asarray(ref), atol=1e-12)


@pytest.mark.parametrize("S,D,causal,window", [
    (64, 32, True, None), (100, 32, False, None), (128, 64, True, 24),
    (96, 16, False, 40), (33, 32, True, None),
])
def test_flash_attention_sweep(S, D, causal, window):
    B, H = 2, 3
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, window=window, bq=32, bk=32)
    r = flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_flash_attention_bf16():
    B, H, S, D = 1, 2, 64, 32
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.bfloat16)
    k = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.bfloat16)
    v = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.bfloat16)
    o = flash_attention(q, k, v, bq=32, bk=32)
    r = flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32), atol=0.06)


def _step_problem(n, d, dtype):
    # a generator of the problem's own, so a case's data does not hang on
    # which tests ran before it in the process
    rng = np.random.default_rng([n, d])
    X = jnp.asarray(rng.normal(size=(n, d)), dtype)
    xij = X[jnp.asarray([3, n - 1])]       # a real WSS pair's feature rows
    sq = jnp.sum(X * X, axis=1)
    f = jnp.asarray(rng.normal(size=(n,)), dtype)
    return f, X, xij, sq, jnp.asarray(0.37, dtype)


@pytest.mark.parametrize("n,d,bm,bk", [
    (257, 9, 64, 64),     # ragged n, d < bk (feature axis fully padded)
    (100, 130, 64, 64),   # ragged on both axes, multi-step k loop
    (120, 40, 32, 16),    # multi-block on both axes
    (160, 13, 40, 13),    # bm divides n over 4 lane-dense blocks, bk = d
    (150, 13, 40, 13),    # bm does not divide n: padded rows, bk = d
    (1000, 13, 96, 13),   # the f update at n = 1000, 11 blocks, last ragged
    (10_000, 9, 1024, 9),  # the f update at n = 10,000, last block ragged
    (300, 16, 128, 16),   # lane blocks of 128, n not a multiple: 84 pad lanes
    (600, 13, 256, 16),   # d not a multiple of 8: 3 zero feature rows
    (700, 21, 256, 8),    # d wider than bk: three feature steps, padded to 24
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_fused_smo_step_ragged(n, d, bm, bk, dtype):
    f, X, xij, sq, delta = _step_problem(n, d, dtype)
    out = fused_smo_step(f, X, xij, sq, delta, gamma=0.5, bm=bm, bk=bk)
    ref = fused_smo_step_ref(f, X, xij, sq, delta, 0.5)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12
    assert out.shape == (n,) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


def test_fused_smo_step_full_block_bitwise():
    """Default (full-array) blocks replay the oracle's fp ops (DESIGN.md
    §Pallas sources). The kernel rows are the oracle's; the rank-2 update
    may fuse differently in the two programs (an FMA in one, a multiply
    and an add in the other), which under jax 0.9 moves 1 element in 150
    by 1 ulp — so the contract is at most 1 ulp, not bitwise. The
    solver-level FusedRBF/PallasRBF parity tests in tests/test_engine.py
    still hold bitwise."""
    f, X, xij, sq, delta = _step_problem(150, 13, jnp.float64)
    out = fused_smo_step(f, X, xij, sq, delta, gamma=0.37)
    ref = fused_smo_step_ref(f, X, xij, sq, delta, 0.37)
    np.testing.assert_array_max_ulp(np.asarray(out), np.asarray(ref),
                                    maxulp=1)


def test_rbf_in_solver_path():
    """The Pallas kernel slots into the SVM pipeline (backend='pallas')."""
    from repro.svm import kernel_matrix
    X = jnp.asarray(RNG.normal(size=(96, 20)), jnp.float64)
    K1 = kernel_matrix(X, X, gamma=0.3, backend="pallas")
    K2 = kernel_matrix(X, X, gamma=0.3, backend="jnp")
    np.testing.assert_allclose(np.asarray(K1), np.asarray(K2), atol=1e-10)
