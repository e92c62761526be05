"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def rbf_kernel_matrix_ref(X, Z, gamma):
    xn = jnp.sum(X * X, -1)[:, None]
    zn = jnp.sum(Z * Z, -1)[None, :]
    d2 = jnp.maximum(xn + zn - 2.0 * (X @ Z.T), 0.0)
    return jnp.exp(-gamma * d2)


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """q,k,v: (B, H, S, D) -> (B, H, S, D); plain softmax attention."""
    S, T = q.shape[2], k.shape[2]
    scores = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(T)[None, :]
    mask = jnp.ones((S, T), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", probs.astype(q.dtype), v)


def fused_smo_step_ref(f, X, xij, sq_norms, delta, gamma):
    """Fused pair-rows + rank-2 update: the FusedRBF.rows2 expression,
    rows upcast to f's dtype before the update (the engine's order)."""
    cross = X @ xij.T
    d2 = jnp.maximum(sq_norms[:, None] + jnp.sum(xij * xij, 1)[None]
                     - 2.0 * cross, 0.0)
    K2 = jnp.exp(-gamma * d2).astype(f.dtype)
    return f + delta * (K2[:, 0] - K2[:, 1])
