"""The precision policy (f32 kernel values, f64 state) held against the
float64 dense reference, with the tolerances DESIGN.md §Precision policy
states: fold accuracy identical, full-set gap <= tol under the f64 kernel,
dual objective within ``OBJ_RTOL``."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cv
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import DenseKernel, dual_objective, kernel_matrix
from repro.svm.engine import optimality, solve
from repro.svm.precision import kdot, kernel_input

TOL = 1e-3
#: relative dual-objective bound between the policy and the f64 reference
#: (measured: <= 7e-7 at adult n = 3,000, k = 10, every method)
OBJ_RTOL = 1e-5


def _f64_input(X):
    return jnp.asarray(X, jnp.float64)


@pytest.mark.parametrize("method", ["cold", "sir", "mir", "ato"])
def test_run_cv_policy_matches_f64_reference(method, monkeypatch):
    ds = make_dataset("adult", n_override=400)
    got = cv.run_cv(ds, k=5, method=method, tol=TOL)
    monkeypatch.setattr(cv, "kernel_input", _f64_input)
    ref = cv.run_cv(ds, k=5, method=method, tol=TOL)
    assert all(f.converged for f in got.folds)
    assert [f.acc_correct for f in got.folds] == \
        [f.acc_correct for f in ref.folds]
    for g, r in zip(got.folds, ref.folds):
        assert abs(g.objective - r.objective) <= OBJ_RTOL * abs(r.objective)


@pytest.mark.parametrize("name", ["adult", "heart", "webdata"])
def test_f32_solution_is_optimal_for_the_f64_problem(name):
    """An alpha solved over f32 kernel values meets gap <= tol on its own
    (full-set) f, and against f recomputed from scratch with the f64
    kernel it misses tol by at most the kernel rounding's reach:
    2 * max_i sum_j |K32_ij - K64_ij| * alpha_j (f moves by at most that
    sum per row, so b_low - b_up by at most twice it)."""
    ds = make_dataset(name, n_override=300)
    y = jnp.asarray(ds.y, jnp.float64)
    n = y.shape[0]
    mask = jnp.ones(n, bool).at[jnp.asarray(kfold_chunks(n, 5)[0])].set(False)
    K32 = kernel_matrix(kernel_input(ds.X), kernel_input(ds.X),
                        gamma=ds.gamma)
    K64 = kernel_matrix(jnp.asarray(ds.X), jnp.asarray(ds.X), gamma=ds.gamma)
    assert K32.dtype == jnp.float32 and K64.dtype == jnp.float64
    res = solve(DenseKernel(K32), y, mask, ds.C, jnp.zeros(n), -y, tol=TOL)
    assert res.alpha.dtype == jnp.float64 and bool(res.converged)
    own = float(optimality(res.alpha, res.f, y, mask, ds.C)[2])
    assert own <= TOL, own
    f64 = K64 @ (res.alpha * y) - y
    gap = float(optimality(res.alpha, f64, y, mask, ds.C)[2])
    reach = float(jnp.max(jnp.abs(K32.astype(jnp.float64) - K64)
                          @ res.alpha))
    # f32 rounding of K (and of d2 before the exp), not a modelling error
    assert reach <= 1e-6 * float(jnp.sum(res.alpha)), reach
    assert gap <= TOL + 2 * reach, (gap, reach)
    ref = solve(DenseKernel(K64), y, mask, ds.C, jnp.zeros(n), -y, tol=TOL)
    obj, obj_ref = (float(dual_objective(K64, y, a))
                    for a in (res.alpha, ref.alpha))
    assert abs(obj - obj_ref) <= OBJ_RTOL * abs(obj_ref)


def test_kdot_keeps_f64_vector_digits():
    """f32 K times an f64 vector: the hi/lo split keeps v's f64 digits,
    leaving only f32 accumulation error (~1e-7 of sum |K_ij v_j|)."""
    rng = np.random.default_rng(0)
    K = jnp.asarray(rng.random((64, 256)), jnp.float32)
    v = jnp.asarray(rng.normal(size=256) * 100.0)
    got = kdot(K, v)
    exact = np.asarray(K, np.float64) @ np.asarray(v)
    assert got.dtype == jnp.float64
    scale = np.abs(np.asarray(K, np.float64)) @ np.abs(np.asarray(v))
    assert np.all(np.abs(np.asarray(got) - exact) <= 1e-6 * scale)
    # same dtypes: the plain product (the reference path), bit for bit
    K64 = K.astype(jnp.float64)
    np.testing.assert_array_equal(np.asarray(kdot(K64, v)),
                                  np.asarray(K64 @ v))
