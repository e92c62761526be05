"""Pallas TPU kernel: fused SMO rank-2 indicator update.

Each SMO iteration updates every optimality indicator:
f += delta * (K_i - K_j). At scale this is THE per-iteration memory-bound
loop (two kernel-row streams + one read-modify-write stream). The fusion
keeps a single pass over HBM; blocks are (8, 1024)-aligned VPU tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.rbf import _I0, auto_interpret, check_compiled_operands


def _fupdate_kernel(f_ref, ki_ref, kj_ref, delta_ref, o_ref):
    o_ref[...] = f_ref[...] + delta_ref[0, 0] * (ki_ref[...] - kj_ref[...])


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def smo_f_update(f, K_i, K_j, delta, *, block: int = 8192,
                 interpret: bool | None = None):
    """f, K_i, K_j: (n,); delta scalar -> updated f.

    ``interpret=None`` auto-detects (Python kernel body on CPU, compiled
    elsewhere) — see :func:`repro.kernels.rbf.auto_interpret`.
    """
    interpret = auto_interpret(interpret)
    if not interpret:
        check_compiled_operands("smo_f_update", f, K_i, K_j)
    n = f.shape[0]
    pad = (-n) % block
    fp = jnp.pad(f, (0, pad))[None, :]
    kip = jnp.pad(K_i, (0, pad))[None, :]
    kjp = jnp.pad(K_j, (0, pad))[None, :]
    d = jnp.asarray(delta, f.dtype).reshape(1, 1)
    out = pl.pallas_call(
        _fupdate_kernel,
        grid=((n + pad) // block,),
        in_specs=[
            pl.BlockSpec((1, block), lambda i: (_I0, i)),
            pl.BlockSpec((1, block), lambda i: (_I0, i)),
            pl.BlockSpec((1, block), lambda i: (_I0, i)),
            pl.BlockSpec((1, 1), lambda i: (_I0, _I0)),
        ],
        out_specs=pl.BlockSpec((1, block), lambda i: (_I0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), f.dtype),
        interpret=interpret,
    )(fp, kip, kjp, d)
    return out[0, :n]
