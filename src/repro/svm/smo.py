"""Dense LibSVM-parity SMO solver — a thin wrapper over the unified engine.

The iteration core (Keerthi-style working-set selection, box-clipped rank-2
update, duality-gap termination) lives in ``repro.svm.engine`` exactly once;
this module binds it to a precomputed kernel matrix (``DenseKernel`` source)
and keeps the historical call signature.

Design notes (unchanged semantics)
----------------------------------
* One compiled solver serves every fold of k-fold CV: fold membership is a
  boolean ``train_mask`` over the padded instance axis, so shapes are static
  and the k-fold loop never retraces.
* The optimality-indicator vector ``f`` (paper Eq. 2, f_i = w.phi(x_i) - y_i)
  is maintained for ALL instances — masked (held-out) entries receive the
  same rank-2 updates, so after a solve ``f`` is globally consistent with
  ``alpha``. The seeding algorithms (MIR in particular) rely on this.
* Working-set selection: WSS-2 (LibSVM's second-order pair selection) by
  default; WSS-1 (maximal violating pair) available for ablation.
* The pairwise update preserves sum(y * alpha) exactly (up to fp error) —
  seeded initial alphas MUST satisfy the equality constraint; the seeding
  module repairs them before calling the solver.

New in the engine era: ``chunk_iters``/``on_chunk`` expose the engine's
chunked dispatch for mid-fold checkpointing, and ``n_iter0`` resumes the
iteration count of a restored partial solve (see DESIGN.md §Chunked
dispatch). Defaults replay the old monolithic behaviour bit-exactly.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.svm.engine import (DenseKernel, SMOResult, _sets,  # noqa: F401
                              solve)
from repro.svm.precision import kdot


def init_f(K: jnp.ndarray, y: jnp.ndarray, alpha: jnp.ndarray) -> jnp.ndarray:
    """f_i = sum_j alpha_j y_j K_ij - y_i, for all i (masked or not)."""
    return kdot(K, alpha * y) - y


def dual_objective(K: jnp.ndarray, y: jnp.ndarray, alpha: jnp.ndarray) -> jnp.ndarray:
    """Paper Problem (1): sum(alpha) - 0.5 aT Q a with Q_ij = y_i y_j K_ij."""
    v = alpha * y
    return jnp.sum(alpha) - 0.5 * (v @ kdot(K, v))


def smo_solve(K: jnp.ndarray, y: jnp.ndarray, train_mask: jnp.ndarray,
              C: float, alpha0: jnp.ndarray, f0: jnp.ndarray,
              tol: float = 1e-3, max_iter: int = 10_000_000,
              wss: str = "2", chunk_iters: int | None = None,
              on_chunk=None, n_iter0: int = 0) -> SMOResult:
    """Solve the masked dual SVM with SMO, warm-started at (alpha0, f0).

    ``f0`` must equal ``init_f(K, y, alpha0)`` (callers use ``init_f`` or the
    incrementally-maintained ``f`` of a previous solve). For a cold start,
    ``alpha0 = 0`` gives ``f0 = -y`` with no matvec.
    """
    return solve(DenseKernel(K), y, train_mask, C, alpha0, f0, tol=tol,
                 max_iter=max_iter, wss=wss, chunk_iters=chunk_iters,
                 on_chunk=on_chunk, n_iter0=n_iter0)

