"""Kernel functions.

``kernel_matrix`` is the compute hot-spot of the whole paper pipeline —
LibSVM's time is dominated by kernel-row evaluation. On TPU the Pallas
kernel in ``repro.kernels.rbf`` computes the same tiled quantity on the
MXU; this module is the pure-jnp reference path (and the CPU path).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


@jax.jit
def rbf_kernel(X: jnp.ndarray, Z: jnp.ndarray, gamma: float) -> jnp.ndarray:
    """K[i,j] = exp(-gamma * ||x_i - z_j||^2), shapes (n,d),(m,d) -> (n,m).

    One fused program: run op by op, the cross-term, its scaled copy and
    the exp argument would each be a live (n, m) temporary (three times
    K's 4.2 GB at adult's published size)."""
    xn = jnp.sum(X * X, axis=-1)[:, None]
    zn = jnp.sum(Z * Z, axis=-1)[None, :]
    d2 = jnp.maximum(xn + zn - 2.0 * (X @ Z.T), 0.0)
    return jnp.exp(-gamma * d2)


def linear_kernel(X: jnp.ndarray, Z: jnp.ndarray, gamma: float = 0.0) -> jnp.ndarray:
    del gamma
    return X @ Z.T


_KERNELS = {"rbf": rbf_kernel, "linear": linear_kernel}


def kernel_matrix(X: jnp.ndarray, Z: jnp.ndarray, *, kind: str = "rbf",
                  gamma: float = 1.0, backend: str = "jnp") -> jnp.ndarray:
    """Full kernel matrix. ``backend='pallas'`` uses the TPU Pallas tile
    kernel (validated in interpret mode on CPU)."""
    if backend == "pallas" and kind == "rbf":
        from repro.kernels.ops import rbf_kernel_matrix  # lazy: optional path
        return rbf_kernel_matrix(X, Z, gamma)
    return _KERNELS[kind](X, Z, gamma)
