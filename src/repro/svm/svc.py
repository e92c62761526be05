"""Classifier-facing API: the ``SVC`` estimator facade plus the bias /
decision-function / accuracy helpers it is built from.

``SVC`` is the intended public entry point for single-model use — fit /
predict / cross_validate over the Study API — so the low-level
``bias_from_solution``/``predict`` pair stops being the de-facto public
interface (they remain exported for the drivers and for power users)."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.svm.precision import STATE_DTYPE, kdot, kernel_input
from repro.svm.smo import SMOResult


def bias_from_solution(res: SMOResult, y: jnp.ndarray, train_mask: jnp.ndarray,
                       C: float) -> jnp.ndarray:
    """b such that decision(x) = sum_i alpha_i y_i K(x_i, x) + b.

    KKT: for 0 < alpha_i < C, f_i = w.x_i - y_i = -b, so b = -mean(f | I_m);
    if the free set is empty fall back to -(b_up + b_low)/2 (LibSVM rule).
    """
    free = train_mask & (res.alpha > 0) & (res.alpha < C)
    n_free = jnp.sum(free)
    mean_f = jnp.sum(jnp.where(free, res.f, 0.0)) / jnp.maximum(n_free, 1)
    fallback = (res.b_up + res.b_low) / 2.0
    return -jnp.where(n_free > 0, mean_f, fallback)


@jax.jit
def decision_function(K_test_train: jnp.ndarray, y_train: jnp.ndarray,
                      alpha: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return kdot(K_test_train, alpha * y_train) + b


def predict(K_test_train, y_train, alpha, b):
    return jnp.where(decision_function(K_test_train, y_train, alpha, b) >= 0, 1, -1)


def accuracy(pred: jnp.ndarray, y_true: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((pred == y_true).astype(jnp.float64))


class SVC:
    """Small estimator facade over the Study API (scikit-learn-flavoured).

    ``fit`` declares the single training solve as a one-lane plan and runs
    it through ``repro.core.study.run_plan`` — the same engine, pool and
    evaluation machinery the CV/grid drivers use — then stores the dual
    solution and recovered bias. ``cross_validate`` forwards to the
    ``run_cv`` plan builder on the fitted hyper-parameters.

    Labels may be any two values; they are mapped to {-1, +1} by sorted
    order and mapped back in ``predict``.
    """

    def __init__(self, C: float = 1.0, gamma: float | str = "scale",
                 kind: str = "rbf", tol: float = 1e-3,
                 max_iter: int = 10_000_000, kernel_backend: str = "jnp",
                 shrink_every: int | str = 0, shrink_quantum: int = 128):
        self.C = float(C)
        self.gamma = gamma
        self.kind = kind
        self.tol = float(tol)
        self.max_iter = int(max_iter)
        self.kernel_backend = kernel_backend
        # active-set shrinking knobs (DESIGN.md §Shrinking): 0 = off
        # (bit-identical solve), "auto" = cost-model verdict
        self.shrink_every = shrink_every
        self.shrink_quantum = int(shrink_quantum)

    def _resolve_gamma(self, X) -> float:
        if self.gamma == "scale":   # sklearn convention: 1 / (d * Var[X])
            return float(1.0 / (X.shape[1] * max(float(jnp.var(X)), 1e-12)))
        return float(self.gamma)

    def _encode(self, y) -> jnp.ndarray:
        y = np.asarray(y)
        self.classes_ = np.unique(y)
        if self.classes_.shape[0] != 2:
            raise ValueError(f"SVC is binary; got classes {self.classes_}")
        return jnp.asarray(np.where(y == self.classes_[1], 1.0, -1.0),
                           jnp.float64)

    def fit(self, X, y) -> "SVC":
        from repro.core.study import Plan, run_plan
        from repro.svm.kernels import kernel_matrix

        y_pm = self._encode(y)
        # gamma="scale" reads the data as given; the kernel reads it at
        # the policy's kernel dtype
        self.gamma_ = self._resolve_gamma(jnp.asarray(X, jnp.float64))
        X = kernel_input(X)
        n = X.shape[0]
        K = kernel_matrix(X, X, kind=self.kind, gamma=self.gamma_,
                          backend=self.kernel_backend)
        from repro.svm.engine import DenseKernel
        plan = Plan(sources={"fit": DenseKernel(K)}, y=y_pm, tol=self.tol,
                    shrink_every=self.shrink_every,
                    shrink_quantum=self.shrink_quantum)
        plan.lane("fit", train_mask=jnp.ones(n, bool), C=self.C,
                  alpha0=jnp.zeros(n, STATE_DTYPE), f0=-y_pm,
                  max_iter=self.max_iter)
        sres = run_plan(plan)
        res = sres.results["fit"]
        self.X_ = X
        self.y_ = y_pm
        self.result_ = res
        self.b_ = bias_from_solution(res, y_pm, jnp.ones(n, bool), self.C)
        self.n_iter_ = int(res.n_iter)
        self.converged_ = bool(res.converged)
        return self

    def decision_function(self, X) -> jnp.ndarray:
        from repro.svm.kernels import kernel_matrix
        Kt = kernel_matrix(kernel_input(X), self.X_,
                           kind=self.kind, gamma=self.gamma_,
                           backend=self.kernel_backend)
        return decision_function(Kt, self.y_, self.result_.alpha, self.b_)

    def predict(self, X) -> np.ndarray:
        pm = np.asarray(self.decision_function(X)) >= 0
        return np.where(pm, self.classes_[1], self.classes_[0])

    def score(self, X, y) -> float:
        return float(np.mean(self.predict(X) == np.asarray(y)))

    def cross_validate(self, X, y, k: int = 10, method: str = "sir", **kw):
        """Alpha-seeded k-fold CV of THIS estimator's hyper-parameters on
        (X, y): builds the dataset record and forwards to the ``run_cv``
        plan builder (all its knobs — checkpointing, chunking, straggler
        policy — pass through ``**kw``). Returns the ``CVReport``."""
        from repro.core.cv import run_cv
        from repro.data.svm_suite import SVMDataset

        if self.kind != "rbf":
            # run_cv computes an RBF kernel; silently cross-validating a
            # different kernel than fit() trains would score the wrong model
            raise ValueError(
                f"cross_validate supports kind='rbf' only (estimator has "
                f"kind={self.kind!r}); run_cv's kernel is RBF")
        X = np.asarray(X, np.float64)
        y_pm = np.asarray(self._encode(y), np.int64)
        ds = SVMDataset(name="svc", X=X, y=y_pm, C=self.C,
                        gamma=self._resolve_gamma(jnp.asarray(X)))
        kw.setdefault("kernel_backend", self.kernel_backend)
        kw.setdefault("shrink_every", self.shrink_every)
        kw.setdefault("shrink_quantum", self.shrink_quantum)
        return run_cv(ds, k=k, method=method, tol=self.tol,
                      max_iter=self.max_iter, **kw)
