"""Pallas TPU kernel: fused WSS-1 kernel-row pair for the SMO rank-2 update.

The paper's cost profile (LibSVM spends its time evaluating Gaussian
kernel rows) says the per-iteration hot loop is the pair of rows K_i, K_j
for the maximal-violating pair plus the indicator update
``f += delta * (K_i - K_j)``. A dense source streams three n-vectors from
HBM per iteration (two kernel rows + the f read-modify-write) *after*
having paid n^2 bytes to materialize K. This kernel never forms K at all:
one blocked pass over X computes both rows, and the ``exp`` fuses on the
VPU. One HBM stream (X plus three n-vectors) per iteration, O(n*d)
resident bytes instead of O(n^2): the TPU-native version of
``FusedRBF.rows2``.

Layout. A blocked launch reads X feature-major, as a (D, N) array
(``feature_major``: features on sublanes, instances on lanes, zero-padded
to whole blocks), in (bk, bm) tiles. The cross term ``X @ [x_i; x_j]^T``
is a float32 multiply-add per element of X for each of the two rows, on
the VPU: the pair's features come in as a (bk, 2) block of per-sublane
columns, broadcast along lanes, and for each 512-lane tile the partial
sums stay in registers across the tile's groups of 8 feature rows, then
one sublane reduction per vreg gives the tile's (2, 512) cross term,
already lane-dense. An MXU product would use 2 of its 128 output columns, and at
``HIGHEST`` push every row of X through six bfloat16 passes. Row norms
stream in and the row pair streams out lane-dense too, as (1, bm) and
(2, bm) blocks of (G, 1, bm) and (G, 2, bm) arrays (G = N / bm).
``PallasRBF`` builds the feature-major copy once, with the source; a
caller that holds only the row-major X gets one built per call.

Precision (``svm/precision.py``): the launch takes the kernel-value
operands only (X, norms, the pair rows — float32 on the chip) and returns
the two rows in that dtype; the products and sums are exact float32
operations. The rank-2 update itself runs outside the launch, in the same
jit, in f's dtype: f is float64 solver state, which no Pallas operand may
be (Mosaic has no f64), and upcasting the rows before the subtraction is
LIBSVM's float-times-double update. The extra traffic is two n-vectors,
against the n*d bytes of X.

Parity contract (the acceptance bar for ``PallasRBF``): in interpret mode
with no blocks given, the launch is the parity configuration — one grid
step over the row-major X, whose body is the jnp expression
``exp(-g*d2)`` that ``FusedRBF.rows2`` evaluates, with the cross term
``X @ xij.T`` — same ops, same accumulation order — and the update
outside is the engine's own expression. The output agrees with the oracle
to within 1 ulp: XLA may fuse the oracle's update differently from this
function's (jax 0.9 moves 1 element in 150 by 1 ulp), and the
engine-level FusedRBF/PallasRBF parity tests pin the bitwise agreement
the solver relies on. Every blocked launch (every compiled one, and
interpret-mode launches given ``bm``/``bk``) takes the VPU form, whose
sum over features need not follow the CPU dot's order: it carries the
usual allclose guarantee, covered by tests/test_kernels.py.

Compiled launches take blocks from ``compiled_blocks``: the whole feature
axis in one step where it fits, and the widest lane block whose
double-buffered tiles fit ``VMEM_BUDGET``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rbf import _I0, auto_interpret, check_compiled_operands

#: VMEM a compiled launch may fill with its double-buffered blocks — half
#: the 16 MiB scoped default, leaving the compiler its own headroom
VMEM_BUDGET = 8 * 1024 * 1024
#: widest lane block; beyond it the per-step overhead is already small
MAX_BM = 4096
#: most feature rows in one step; a wider X takes several
MAX_BK = 2048
_LANES = 128
_SUBLANES = 8
#: lanes of a tile: its four vregs share each broadcast of the pair's
#: features (one vreg alone took 45 µs per call at adult's shape, four
#: 24 µs, on a v5e)
_TILE = 4 * _LANES


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def compiled_blocks(n: int, d: int, itemsize: int = 4) -> tuple[int, int]:
    """(bm, bk) of the feature-major (bk, bm) tile of a compiled launch
    over an (n, d) X.

    ``bk`` is the feature axis rounded up to 8 sublanes, in one step up
    to ``MAX_BK`` rows, else in the fewest equal steps of at most that.
    Per lane a step holds a column of the X tile and of the (1, bm) norm
    and (2, bm) output blocks, each sublane-padded to 8 and
    double-buffered, and of the (2, bm) accumulator. ``bm`` is a multiple
    of the kernel's 512-lane tile: the fewest blocks of at most the widest
    that fits the budget (and ``MAX_BM``) that cover n, each as narrow as
    covering n allows, so N exceeds n by under a tile a block; one block
    narrower than a tile covers a smaller n in whole vregs.
    """
    d8 = _round_up(d, _SUBLANES)
    bk = _round_up(-(-d8 // -(-d8 // MAX_BK)), _SUBLANES)
    per_lane = (2 * bk + 2 * _SUBLANES + 2 * _SUBLANES + _SUBLANES) * itemsize
    cap = max(_TILE, min(MAX_BM, VMEM_BUDGET // per_lane) // _TILE * _TILE)
    blocks = -(-n // cap)
    return min(_round_up(-(-n // blocks), _TILE), _round_up(n, _LANES)), bk


def _blocks(n: int, d: int, itemsize: int, bm: int | None,
            bk: int | None) -> tuple[int, int]:
    """(bm, bk) of a blocked launch: those given, the rest from
    ``compiled_blocks``."""
    auto_bm, auto_bk = compiled_blocks(n, d, itemsize)
    return (auto_bm if bm is None else bm), (auto_bk if bk is None else bk)


def feature_major_shape(n: int, d: int, itemsize: int = 4,
                        bm: int | None = None,
                        bk: int | None = None) -> tuple[int, int]:
    """(D, N) of the feature-major X a launch with blocks ``bm``/``bk``
    reads; a block not given comes from ``compiled_blocks``."""
    bm, bk = _blocks(n, d, itemsize, bm, bk)
    return _round_up(d, bk), _round_up(n, bm)


def feature_major(X, bm: int | None = None, bk: int | None = None):
    """X (n, d) as a blocked launch reads it: (D, N), features on sublanes
    and instances on lanes, zero-padded to whole blocks. Zero features
    change no cross term; zero instances' outputs are sliced off."""
    n, d = X.shape
    D, N = feature_major_shape(n, d, X.dtype.itemsize, bm, bk)
    return jnp.pad(X.T, ((0, D - d), (0, N - n)))


def _parity_kernel(xn_ref, sn2_ref, x_ref, xij_ref, o_ref, cross_ref, *,
                   gamma):
    # FusedRBF.rows2's expression over the whole row-major X. The (n, 2)
    # product goes through VMEM, where XLA would fold a transpose of its
    # value into the dot and sum in another order; the transpose of the
    # stored product moves values without rounding.
    cross_ref[...] = jnp.dot(x_ref[...], xij_ref[...].T,
                             preferred_element_type=cross_ref.dtype,
                             precision=jax.lax.Precision.HIGHEST)
    cross = cross_ref[...].T                                      # (2, n)
    d2 = jnp.maximum(xn_ref[0] + sn2_ref[...] - 2.0 * cross, 0.0)
    o_ref[0] = jnp.exp(-gamma * d2).astype(o_ref.dtype)


def _smo_step_kernel(xn_ref, sn2_ref, xt_ref, pc_ref, o_ref, acc_ref, *,
                     gamma, n_k_steps):
    k_step = pl.program_id(1)
    bk, bm = xt_ref.shape
    # a tile of _TILE lanes, in chunks one vreg wide, takes feature rows a
    # group one vreg tall at a time. Ragged tiles, chunks and groups arise
    # only from blocks an interpret-mode caller gives.
    tile = _TILE if bm % _TILE == 0 else bm
    width = _LANES if tile % _LANES == 0 else tile
    rows = min(_SUBLANES, bk)
    whole, tail = bk - bk % rows, bk % rows

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.loop(_I0, np.int32(bm // tile), step=np.int32(1))
    def _lane_tile(t):
        base = pl.multiple_of(t * np.int32(tile), tile)
        chunks = [pl.ds(base + np.int32(c), width)
                  for c in range(0, tile, width)]
        part = [[jnp.zeros((rows, width), acc_ref.dtype)] * 2
                for _ in chunks]
        for r in range(0, whole, rows):
            # the pair's features of this group, broadcast along lanes
            # once for every chunk of the tile
            col = pc_ref[r:r + rows, :]
            pair = [jnp.broadcast_to(col[:, p:p + 1], (rows, width))
                    for p in range(2)]
            for c, cols in enumerate(chunks):
                x = xt_ref[r:r + rows, cols]
                part[c] = [a + x * b for a, b in zip(part[c], pair)]
        for c, cols in enumerate(chunks):
            cross = [jnp.sum(a, axis=0, keepdims=True) for a in part[c]]
            if tail:
                x = xt_ref[whole:, cols]
                cross = [s + jnp.sum(x * pc_ref[whole:, p:p + 1], axis=0,
                                     keepdims=True)
                         for p, s in enumerate(cross)]
            acc_ref[:, cols] += jnp.concatenate(cross, axis=0)

    @pl.when(k_step == n_k_steps - 1)
    def _finalize():
        d2 = jnp.maximum(xn_ref[0] + sn2_ref[...] - 2.0 * acc_ref[...], 0.0)
        o_ref[0] = jnp.exp(-gamma * d2).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("gamma", "bm", "bk", "interpret"))
def fused_smo_step(f, X, xij, sq_norms, delta, *, gamma: float,
                   bm: int | None = None, bk: int | None = None,
                   interpret: bool | None = None, Xt=None):
    """One fused SMO step: ``f + delta * (K_i - K_j)`` without rows in HBM.

    ``f`` (n,) indicator vector, any float dtype; ``X`` (n, d) training
    matrix; ``xij`` (2, d) the WSS-1 pair's feature rows, read by the
    caller (``PallasRBF._pair`` indexes X, so this launch is the one
    pass over X per SMO iteration);
    ``sq_norms`` (n,) precomputed row norms of X; ``delta`` the clipped
    2-variable step. Returns the updated f in f's dtype.

    ``Xt`` is X in the blocked launch's layout, ``feature_major(X, bm,
    bk)``, which a caller that holds it passes (``PallasRBF`` builds it
    once); without it a blocked launch builds it here, a copy of X per
    call.

    In interpret mode with neither ``bm`` nor ``bk`` the launch is the
    parity configuration over the row-major X (module doc). Otherwise it
    is blocked, a block not given coming from ``compiled_blocks(n, d)``;
    compiled launches refuse f64 kernel operands. ``interpret=None``
    auto-detects the CPU validation path.
    """
    interpret = auto_interpret(interpret)
    n, d = X.shape
    if not interpret:
        check_compiled_operands("fused_smo_step", X, xij, sq_norms,
                                *([] if Xt is None else [Xt]))
    acc_dtype = jnp.float64 if X.dtype == jnp.float64 else jnp.float32
    # norms of the pair rows, from the unpadded rows so the reduction
    # matches FusedRBF.rows2 verbatim
    sn2 = jnp.sum(xij * xij, 1)[:, None].astype(acc_dtype)       # (2, 1)
    if interpret and bm is None and bk is None:
        K2 = pl.pallas_call(
            functools.partial(_parity_kernel, gamma=gamma),
            out_shape=jax.ShapeDtypeStruct((1, 2, n), X.dtype),
            scratch_shapes=[pltpu.VMEM((n, 2), acc_dtype)],
            interpret=True,
        )(sq_norms.astype(acc_dtype).reshape(1, 1, n), sn2, X, xij)[0]
    else:
        bm, bk = _blocks(n, d, X.dtype.itemsize, bm, bk)
        if Xt is None:
            Xt = feature_major(X, bm, bk)
        D, N = Xt.shape
        if (D, N) != feature_major_shape(n, d, X.dtype.itemsize, bm, bk):
            raise ValueError(f"Xt {Xt.shape} is not the feature-major X of "
                             f"an ({n}, {d}) X in ({bk}, {bm}) blocks")
        G, n_k_steps = N // bm, D // bk
        # the pair's features as per-sublane columns; zero rows pad them
        # to X's padded feature axis
        pc = jnp.pad(xij, ((0, 0), (0, D - d))).T                 # (D, 2)
        # row norms along lanes, one (1, bm) row per lane block
        xn = jnp.pad(sq_norms, (0, N - n)).astype(acc_dtype).reshape(G, 1, bm)
        K2 = pl.pallas_call(
            functools.partial(_smo_step_kernel, gamma=gamma,
                              n_k_steps=n_k_steps),
            grid=(G, n_k_steps),
            in_specs=[
                pl.BlockSpec((1, 1, bm), lambda i, k: (i, _I0, _I0)),  # norms
                pl.BlockSpec((2, 1), lambda i, k: (_I0, _I0)),   # pair norms
                pl.BlockSpec((bk, bm), lambda i, k: (k, i)),     # X, (D, N)
                pl.BlockSpec((bk, 2), lambda i, k: (k, _I0)),    # pair, (D, 2)
            ],
            out_specs=pl.BlockSpec((1, 2, bm), lambda i, k: (i, _I0, _I0)),
            out_shape=jax.ShapeDtypeStruct((G, 2, bm), X.dtype),
            scratch_shapes=[pltpu.VMEM((2, bm), acc_dtype)],
            interpret=interpret,
        )(xn, sn2, Xt, pc)
        K2 = jnp.swapaxes(K2, 0, 1).reshape(2, N)[:, :n]
    K2 = K2.astype(f.dtype)
    return f + jnp.asarray(delta, f.dtype) * (K2[0] - K2[1])
