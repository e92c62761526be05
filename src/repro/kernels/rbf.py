"""Pallas TPU kernel: tiled RBF kernel-matrix computation.

THE compute hot-spot of the paper's pipeline: LibSVM spends its time
evaluating Gaussian kernel rows; on TPU we compute K = exp(-g*d2(X,Z)) as a
blocked matmul — the cross-term X @ Z^T runs on the MXU over (BM, BN)
output tiles with a BK-chunked contraction accumulated in an f32 VMEM
scratch; row norms stream in as (BM,1)/(1,BN) tiles and the exp() fuses on
the VPU at the final contraction step. This is the TPU-native adaptation of
the paper's kernel-cache design (recompute beats irregular caches on MXU).

Block sizes default to MXU-aligned (128, 128, 512): VMEM footprint
= BM*BK + BK*BN (bf16/f32 inputs) + BM*BN*4 (acc) ~ 0.6 MB << 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: block index for an axis a BlockSpec does not tile. Index maps must
#: return int32: under x64 a Python ``0`` traces as int64, and Mosaic then
#: refuses the index map ("failed to legalize operation 'func.return'")
_I0 = np.int32(0)


def check_compiled_operands(name: str, *operands) -> None:
    """Refuse f64 operands for a compiled launch: Mosaic lowers no f64,
    and the precision policy (``svm/precision.py``) keeps kernel values
    in f32. Interpret mode keeps f64 as the tiling-validation oracle."""
    for a in operands:
        if jnp.dtype(a.dtype) == jnp.float64:
            raise TypeError(
                f"{name}: compiled Pallas launches take float32 kernel "
                f"operands, got {a.dtype}; build sources with "
                "repro.svm.precision.kernel_input")


def auto_interpret(interpret: bool | None) -> bool:
    """Resolve an ``interpret=None`` default: interpret mode (kernel body
    run in Python) only when the backend has no Mosaic compiler — i.e. the
    CPU validation path. TPU callers get compiled kernels without passing
    a flag."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)


def _rbf_kernel(xn_ref, zn_ref, x_ref, z_ref, o_ref, acc_ref, *, gamma,
                n_k_steps):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], z_ref[...].T,
                            preferred_element_type=acc_ref.dtype,
                            precision=jax.lax.Precision.HIGHEST)

    @pl.when(k_step == n_k_steps - 1)
    def _finalize():
        d2 = xn_ref[...] + zn_ref[...] - 2.0 * acc_ref[...]
        d2 = jnp.maximum(d2, 0.0)
        o_ref[...] = jnp.exp(-gamma * d2).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gamma", "bm", "bn", "bk", "interpret"))
def rbf_kernel_matrix(X, Z, gamma: float, *, bm: int = 128, bn: int = 128,
                      bk: int = 512, interpret: bool | None = None):
    """K[i,j] = exp(-gamma * ||X_i - Z_j||^2); X (n,d), Z (m,d) -> (n,m).

    ``interpret=None`` auto-detects: the kernel body runs in Python on
    CPU (validation mode for this container) and compiles elsewhere.
    """
    interpret = auto_interpret(interpret)
    if not interpret:
        check_compiled_operands("rbf_kernel_matrix", X, Z)
    n, d = X.shape
    m = Z.shape[0]
    pad_n = (-n) % bm
    pad_m = (-m) % bn
    pad_d = (-d) % bk
    Xp = jnp.pad(X, ((0, pad_n), (0, pad_d)))
    Zp = jnp.pad(Z, ((0, pad_m), (0, pad_d)))
    # accumulate in f64 only for f64 inputs (TPU path is f32; interpret
    # mode validates the f64 LibSVM-parity path bit-accurately)
    acc_dtype = jnp.float64 if X.dtype == jnp.float64 else jnp.float32
    xn = jnp.sum(Xp * Xp, -1, keepdims=True).astype(acc_dtype)    # (N,1)
    zn = jnp.sum(Zp * Zp, -1, keepdims=True).T.astype(acc_dtype)  # (1,M)
    N, M, D = n + pad_n, m + pad_m, d + pad_d
    n_k_steps = D // bk

    out = pl.pallas_call(
        functools.partial(_rbf_kernel, gamma=gamma, n_k_steps=n_k_steps),
        grid=(N // bm, M // bn, n_k_steps),
        in_specs=[
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, _I0)),
            pl.BlockSpec((1, bn), lambda i, j, k: (_I0, j)),
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bk), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((N, M), X.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), acc_dtype)],
        interpret=interpret,
    )(xn, zn, Xp, Zp)
    return out[:n, :m]
