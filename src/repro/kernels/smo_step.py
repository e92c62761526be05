"""Pallas TPU kernel: fused WSS-1 kernel-row pair for the SMO rank-2 update.

The paper's cost profile (LibSVM spends its time evaluating Gaussian
kernel rows) says the per-iteration hot loop is the pair of rows K_i, K_j
for the maximal-violating pair plus the indicator update
``f += delta * (K_i - K_j)``. A dense source streams three n-vectors from
HBM per iteration (two kernel rows + the f read-modify-write) *after*
having paid n^2 bytes to materialize K. This kernel never forms K at all:
one blocked pass over X computes both rows — the cross-term
``X @ [x_i; x_j]^T`` runs on the MXU into a (BM, 2) VMEM accumulator over
a BK-chunked contraction, and at the final contraction step the finished
tile is transposed to (2, BM) and the ``exp`` fuses on the VPU. Row norms
stream in and the row pair streams out lane-dense, as (1, BM) and
(2, BM) blocks of (G, 1, BM) and (G, 2, BM) arrays (G = N / BM), so no
per-row vector is padded to 128 lanes in HBM. One HBM stream (X plus
three n-vectors) per iteration, O(n*d) resident bytes instead of O(n^2):
the TPU-native version of ``FusedRBF.rows2``.

Precision (``svm/precision.py``): the launch takes the kernel-value
operands only (X, norms, the pair rows — float32 on the chip) and returns
the two rows in that dtype. The rank-2 update itself runs outside the
launch, in the same jit, in f's dtype: f is float64 solver state, which
no Pallas operand may be (Mosaic has no f64), and upcasting the rows
before the subtraction is LIBSVM's float-times-double update. The extra
traffic is two n-vectors, against the n*d bytes of X.

Parity contract (the acceptance bar for ``PallasRBF``): with full-array
blocks (``bm=n``, ``bk=d`` — the interpret-mode default) there is no
padding and a single grid step, so the kernel body is the jnp expression
``exp(-g*d2)`` that ``FusedRBF.rows2`` evaluates — same ops, same
accumulation order, each element of d2 from the same operands (the
transpose only moves values) — and the update outside is the engine's own
expression. The output agrees with the oracle to within 1 ulp: XLA may
fuse the oracle's update differently from this function's (jax 0.9 moves
1 element in 150 by 1 ulp), and the engine-level FusedRBF/PallasRBF
parity tests pin the bitwise agreement the solver relies on. Blocked
launches (the compiled TPU configuration) change the contraction split
and carry only the usual allclose guarantee, covered by
tests/test_kernels.py.

Compiled launches take blocks from ``compiled_blocks``: the whole feature
axis in one contraction step where it fits, and the tallest row block
whose double-buffered tiles fit ``VMEM_BUDGET`` — a divisor of n where
one exists, so X is never copied into a padded buffer per iteration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rbf import _I0, auto_interpret, check_compiled_operands

#: VMEM a compiled launch may fill with its double-buffered blocks — half
#: the 16 MiB scoped default, leaving the compiler its own headroom
VMEM_BUDGET = 8 * 1024 * 1024
#: tallest row block; beyond it the per-step overhead is already small
MAX_BM = 4096
_LANES = 128
_SUBLANES = 8


def compiled_blocks(n: int, d: int, itemsize: int = 4) -> tuple[int, int]:
    """(bm, bk) for a compiled launch over an (n, d) X.

    ``bk`` is the whole feature axis (a block dim equal to the array dim
    needs no 128-alignment) up to 512 columns, else 512. Per row of ``bm``
    a step holds the X tile and the (bm, 2) accumulator, each lane-padded
    to 128, and a column of the (1, bm) norm and (2, bm) output blocks,
    each sublane-padded to 8; all but the scratch are double-buffered.
    ``bm`` is the largest multiple of 8 dividing n that fits the budget (no
    padding), else the largest power of two.
    """
    bk = d if d <= 512 else 512
    lanes_x = -(-bk // _LANES) * _LANES
    per_row = (2 * lanes_x + 2 * _SUBLANES + 2 * _SUBLANES + _LANES) * itemsize
    cap = min(MAX_BM, VMEM_BUDGET // per_row)
    cap -= cap % 8
    divisors = [m for m in range(8, min(cap, n) + 1, 8) if n % m == 0]
    if divisors and divisors[-1] >= cap // 4:
        return divisors[-1], bk
    bm = 8
    while bm * 2 <= cap and bm < n:
        bm *= 2
    return bm, bk


def _smo_step_kernel(xn_ref, sn2_ref, x_ref, xij_ref, o_ref, acc_ref, *,
                     gamma, n_k_steps):
    k_step = pl.program_id(1)
    prod = jnp.dot(x_ref[...], xij_ref[...].T,
                   preferred_element_type=acc_ref.dtype,
                   precision=jax.lax.Precision.HIGHEST)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = prod

    @pl.when(k_step > 0)
    def _accumulate():
        acc_ref[...] += prod

    @pl.when(k_step == n_k_steps - 1)
    def _finalize():
        # the finished (bm, 2) cross term turned lane-dense; a transpose
        # moves values without rounding, so each element sees the same ops
        cross = acc_ref[...].T                                  # (2, bm)
        d2 = jnp.maximum(xn_ref[0] + sn2_ref[...] - 2.0 * cross, 0.0)
        o_ref[0] = jnp.exp(-gamma * d2).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gamma", "bm", "bk", "interpret"))
def fused_smo_step(f, X, xij, sq_norms, delta, *, gamma: float,
                   bm: int | None = None, bk: int | None = None,
                   interpret: bool | None = None):
    """One fused SMO step: ``f + delta * (K_i - K_j)`` without rows in HBM.

    ``f`` (n,) indicator vector, any float dtype; ``X`` (n, d) training
    matrix; ``xij`` (2, d) the WSS-1 pair's feature rows, read by the
    caller (``PallasRBF._pair`` indexes X, so this launch is the one
    pass over X per SMO iteration);
    ``sq_norms`` (n,) precomputed row norms of X; ``delta`` the clipped
    2-variable step. Returns the updated f in f's dtype.

    ``bm``/``bk`` default to full-array blocks (n, d) in interpret mode —
    no padding, single contraction step, the parity configuration — and
    to ``compiled_blocks(n, d)`` for compiled launches, which refuse f64
    kernel operands. ``interpret=None`` auto-detects the CPU validation
    path.
    """
    interpret = auto_interpret(interpret)
    n, d = X.shape
    if not interpret:
        check_compiled_operands("fused_smo_step", X, xij, sq_norms)
    if bm is None or bk is None:
        auto_bm, auto_bk = ((n, d) if interpret
                            else compiled_blocks(n, d, X.dtype.itemsize))
        bm = auto_bm if bm is None else bm
        bk = auto_bk if bk is None else bk
    # norms of the pair rows, computed before any padding so the reduction
    # matches FusedRBF.rows2 verbatim
    acc_dtype = jnp.float64 if X.dtype == jnp.float64 else jnp.float32
    sn2 = jnp.sum(xij * xij, 1)[:, None].astype(acc_dtype)       # (2, 1)
    pad_n, pad_d = (-n) % bm, (-d) % bk
    # zero feature columns leave cross-terms and norms unchanged; padded
    # rows are sliced off the output. A block that divides the array
    # needs no padded copy of X.
    Xp, xijp = X, xij
    if pad_n or pad_d:
        Xp = jnp.pad(X, ((0, pad_n), (0, pad_d)))
        xijp = jnp.pad(xij, ((0, 0), (0, pad_d)))
    N, D = n + pad_n, d + pad_d
    G, n_k_steps = N // bm, D // bk
    # row norms along lanes, one (1, bm) row per row block: a block dim
    # equal to the array dim needs no 128-alignment, so any bm is legal
    xn = jnp.pad(sq_norms, (0, pad_n)).astype(acc_dtype).reshape(G, 1, bm)

    K2 = pl.pallas_call(
        functools.partial(_smo_step_kernel, gamma=gamma,
                          n_k_steps=n_k_steps),
        grid=(G, n_k_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bm), lambda i, k: (i, _I0, _I0)),  # row norms
            pl.BlockSpec((2, 1), lambda i, k: (_I0, _I0)),     # pair norms
            pl.BlockSpec((bm, bk), lambda i, k: (i, k)),       # X
            pl.BlockSpec((2, bk), lambda i, k: (_I0, k)),      # pair rows
        ],
        out_specs=pl.BlockSpec((1, 2, bm), lambda i, k: (i, _I0, _I0)),
        out_shape=jax.ShapeDtypeStruct((G, 2, bm), X.dtype),
        scratch_shapes=[pltpu.VMEM((bm, 2), acc_dtype)],
        interpret=interpret,
    )(xn, sn2, Xp, xijp)
    K2 = jnp.swapaxes(K2, 0, 1).reshape(2, N)[:, :n].astype(f.dtype)
    return f + jnp.asarray(delta, f.dtype) * (K2[0] - K2[1])
