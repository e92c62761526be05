"""The SVM stack's precision policy — LIBSVM's, on every backend.

LIBSVM stores kernel values as ``float`` (its ``Qfloat``) and keeps the
gradient, the alphas, C and the optimality gap in ``double``. This repo
does the same, whatever device it runs on:

* **kernel operands** — the dense K and the X a row-streaming source reads
  — are ``KERNEL_DTYPE`` (float32). A TPU has no float64 units and Pallas
  lowers no float64 operand, and f32 halves the n² bytes of a dense K;
* **solver state** — y, alpha, f, C and the gap — is ``STATE_DTYPE``
  (float64), so the rank-2 updates accumulate like LIBSVM's ``G[k] +=
  Q_i[k] * delta`` (a float times a double, added in double).

Kernel values enter the state arithmetic upcast to float64, never the
other way round. The CV entry points apply the policy where they build
sources (``kernel_input``); a float64 K handed to a source directly is still
solved in float64 end to end — the reference the tests hold the policy
against (DESIGN.md §Precision policy).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KERNEL_DTYPE = jnp.float32
STATE_DTYPE = jnp.float64


def kernel_input(X) -> jnp.ndarray:
    """``X`` as the kernel-operand dtype: every CV entry point builds its
    kernel sources from this, so CPU tests exercise the numbers the chip
    runs."""
    return jnp.asarray(X, KERNEL_DTYPE)


def kdot(K, v, *, span: int | None = None) -> jnp.ndarray:
    """``K @ v`` for kernel values ``K`` (t, n) and a state vector ``v``
    (n,), returned in ``v``'s dtype.

    For an f32 K and an f64 v, v is split into f32 high and low parts and
    both go through ONE f32 matmul (no f64 copy of K, which at n = 32,561
    would not fit a 16 GB chip); the two halves are summed in f64. What
    remains is f32 accumulation error — about 1e-7 of ``sum |K_ij v_j|`` —
    which the tolerances in DESIGN.md §Precision policy account for. With
    ``span`` (which must divide n) the f32 sums run over ``span`` columns
    at a time and f64 sums over the partial sums, so the f32 accumulation
    covers ``span`` terms, not n. Same dtypes take the plain product (the
    float64 reference path)."""
    if K.dtype == v.dtype:
        return K @ v
    hi = v.astype(K.dtype)
    lo = (v - hi.astype(v.dtype)).astype(K.dtype)
    V = jnp.stack([hi, lo], axis=-1)
    if span is not None:
        t, n = K.shape
        part = jnp.einsum("tcj,cjf->tcf", K.reshape(t, n // span, span),
                          V.reshape(n // span, span, 2),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=K.dtype)
        out = jnp.sum(part.astype(v.dtype), axis=1)
        return out[..., 0] + out[..., 1]
    out = jnp.dot(K, V, precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=K.dtype)
    return out[..., 0].astype(v.dtype) + out[..., 1].astype(v.dtype)
