"""Step kind ``fold_chain``: the paper's k-fold protocol, one fold per step.

One step is ``repro.core.study.run_plan`` on the plan that ``run_cv``
(dense sources) or ``run_cv_batched`` (``pallas_rbf``) builds for one
fold, followed by the held-out evaluation those entry points run:

* the previous fold's result enters as a given lane;
* this fold is a lane seeded from it by the ``"fold"`` transform with the
  traffic's method, or, for ``cold``, a lane started from zero;
* the kernel source is built once in set-up: the dense float32 K, or the
  matrix-free ``PallasRBF`` over X.

Folds run in a ring: after fold k-1 comes fold 0, seeded from fold k-1,
so every step of the window is the same kind of work. ``setup`` solves
the folds before the window's first, fold 2, that compile every program
the window runs: fold 1 for ``cold``; folds 0 and 1 for a seeded method,
whose fold 0 runs cold and fold 1 runs the seed transform. The one
difference from ``run_cv``: the per-plan host costs (plan validation and
analysis, pool construction) are paid once per fold, not once per CV.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.study import Plan, run_plan
from repro.svm import (DenseKernel, PallasRBF, bias_from_solution,
                       dual_objective, kernel_matrix, predict)
from repro.svm.precision import STATE_DTYPE, kernel_input

import data


@dataclasses.dataclass
class Fold:
    """What one step produced, as the program returned it."""
    fold: int
    seed_from: int
    alpha: object
    f: object
    pred: np.ndarray
    objective: float
    n_iter: int
    converged: bool
    seed_s: float
    solve_s: float


@jax.jit
def _streamed_objective(source, y, alpha):
    """``run_cv_batched``'s dual objective for a row-streaming source,
    under one jit: eagerly, ``matvec``'s ``lax.map`` traces and compiles
    anew at every call, which would put a compile in every fold."""
    v = alpha * y
    return jnp.sum(alpha) - 0.5 * jnp.dot(v, source.matvec(v))


class FoldChain:
    """The fold ring of one cell (see the module docstring)."""

    def __init__(self, cfg: dict, traffic: dict, X, y, chunks):
        self.cfg, self.traffic = cfg, traffic
        self.method = traffic["method"]
        self.chunks = chunks
        self.k, n = chunks.shape[0], chunks.size
        self.X = kernel_input(X[:n])
        self.y = jnp.asarray(y[:n], STATE_DTYPE)
        self.masks = jnp.asarray(data.train_masks(chunks))
        self.test = [jnp.asarray(c) for c in chunks]
        self.chunk_iters = traffic["chunk_iters"] or cfg["max_iter"]
        self.transitions = {}
        if self.method != "cold":
            for h in range(self.k):
                g = (h - 1) % self.k
                self.transitions[h] = tuple(
                    jnp.asarray(ix) for ix in data.transition_idx(chunks, g, h))
        self.source = self.K = None
        self.prev = self.prev_result = None
        self.next_fold = 0

    def setup(self) -> list[Fold]:
        """Build the kernel source and solve the folds before fold 2: only
        a seeded fold runs programs that a cold fold does not."""
        if self.traffic["source"] == "pallas_rbf":
            self.source = PallasRBF(self.X, self.cfg["gamma"])
            self.source.sq_norms.block_until_ready()
        else:
            self.K = kernel_matrix(self.X, self.X, kind="rbf",
                                   gamma=self.cfg["gamma"])
            self.K.block_until_ready()
            self.source = DenseKernel(self.K)
        if self.method == "cold":
            self.next_fold = 1
            return [self()]
        return [self(), self()]

    def __call__(self) -> Fold:
        """Solve and evaluate the next fold of the ring."""
        h = self.next_fold
        C, tol = self.cfg["C"], self.cfg["tol"]
        plan = Plan(sources={"cv": self.source}, y=self.y, tol=tol,
                    wss=self.traffic["wss"], chunk_iters=self.chunk_iters)
        common = dict(train_mask=self.masks[h], C=C,
                      max_iter=self.cfg["max_iter"])
        g = -1
        if self.method == "cold" or self.prev is None:
            plan.lane(h, alpha0=jnp.zeros_like(self.y), f0=-self.y, **common)
        else:
            g = self.prev.fold
            S, R, T = self.transitions[h]
            plan.lane(g, result=self.prev_result)
            plan.lane(h, dep=g, transform="fold",
                      params=dict(method=self.method, S_idx=S, R_idx=R,
                                  T_idx=T), **common)
        with TraceAnnotation("bench.plan"):
            sres = run_plan(plan)
        res, stat = sres.results[h], sres.stats[h]
        with TraceAnnotation("bench.eval"):
            pred, obj = self.evaluate(h, res)
        fold = Fold(fold=h, seed_from=g, alpha=res.alpha, f=res.f, pred=pred,
                    objective=obj, n_iter=stat.n_iter,
                    converged=stat.converged, seed_s=stat.seed_s,
                    solve_s=stat.solve_s)
        self.prev, self.prev_result = fold, res
        self.next_fold = (h + 1) % self.k
        return fold

    def evaluate(self, h, res):
        """Held-out predictions and dual objective, with the functions
        ``run_cv`` (dense K) and ``run_cv_batched`` (row-streaming
        source) take them with. Over a dense K every row is predicted and
        the held-out ones kept, as ``run_cv`` predicts through one product
        with K: a gather of K's test rows would be a second (n / k, n)
        buffer, which one chip does not hold beside webdata's or mnist's
        K."""
        test = self.test[h]
        b = bias_from_solution(res, self.y, self.masks[h], self.cfg["C"])
        if self.K is not None:
            pred = predict(self.K, self.y, res.alpha, b)[test]
            obj = dual_objective(self.K, self.y, res.alpha)
        else:
            pred = predict(self.source.rows_at(test), self.y, res.alpha, b)
            obj = _streamed_objective(self.source, self.y, res.alpha)
        pred, obj = jax.device_get((pred, obj))
        return np.asarray(pred), float(obj)

    def close(self) -> None:
        """Free the dense K and drop every device array of the chain."""
        if self.K is not None:
            self.K.delete()
        self.source = self.K = self.prev_result = None
        self.X = self.y = self.masks = None
        self.test, self.transitions = [], {}


STEP = FoldChain
