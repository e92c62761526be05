"""Paper-claim validation + property tests for the seeding algorithms."""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    # property tests degrade to explicit skips; everything else still runs
    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):
        def deco(fn):
            @functools.wraps(fn)
            @pytest.mark.skip(reason="hypothesis not installed: property "
                                     "test skipped (pip install hypothesis)")
            def stub():
                pass
            return stub
        return deco

    def settings(*_a, **_k):
        return lambda fn: fn

    class st:  # noqa: N801 - stand-in for hypothesis.strategies
        integers = staticmethod(lambda *a, **k: None)
        floats = staticmethod(lambda *a, **k: None)

from repro.core import seeding
from repro.core.cv import run_cv, _transition_idx
from repro.data.svm_suite import make_dataset, kfold_chunks
from repro.svm import init_f, kernel_matrix, smo_solve

C_TEST = 4.0


def _fold_setup(name="madelon", n=400, k=5):
    ds = make_dataset(name, n_override=n)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    chunks = kfold_chunks(n, k, seed=0)
    nn = chunks.size
    K, y = K[:nn][:, :nn], y[:nn]
    mask0 = jnp.ones(nn, bool).at[jnp.asarray(chunks[0])].set(False)
    res0 = smo_solve(K, y, mask0, ds.C, jnp.zeros(nn), -y)
    S, R, T = _transition_idx(chunks, 0, 1)
    return ds, K, y, chunks, res0, (S, R, T)


@pytest.mark.parametrize("method", ["mir", "sir", "ato"])
def test_seed_satisfies_constraints(method):
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup()
    alpha0 = seeding.SEEDERS[method](K, y, ds.C, res0, S, R, T)
    eps = 1e-8 * max(ds.C, 1.0)
    assert bool(jnp.all((alpha0 >= -eps) & (alpha0 <= ds.C + eps)))
    # equality over the NEW training set; removed chunk must be zeroed
    assert float(jnp.abs(jnp.sum(alpha0 * y))) < 1e-6 * max(ds.C, 1.0)
    assert float(jnp.abs(alpha0[R]).max()) == 0.0


@pytest.mark.parametrize("method", ["mir", "sir", "ato"])
def test_identical_results_claim(method):
    """Paper Table 1: seeding changes the starting point, not the result.
    Predictions may only differ where the decision value is within solver
    tolerance of zero (degenerate margins)."""
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup()
    nn = chunks.size
    mask1 = jnp.ones(nn, bool).at[jnp.asarray(chunks[1])].set(False)
    cold = smo_solve(K, y, mask1, ds.C, jnp.zeros(nn), -y)
    alpha0 = seeding.SEEDERS[method](K, y, ds.C, res0, S, R, T)
    warm = smo_solve(K, y, mask1, ds.C, alpha0, init_f(K, y, alpha0))
    from repro.svm import bias_from_solution, decision_function
    bc = bias_from_solution(cold, y, mask1, ds.C)
    bw = bias_from_solution(warm, y, mask1, ds.C)
    t_idx = jnp.asarray(chunks[1])
    dc = decision_function(K[t_idx], y, cold.alpha, bc)
    dw = decision_function(K[t_idx], y, warm.alpha, bw)
    differs = (dc >= 0) != (dw >= 0)
    near_zero = (jnp.abs(dc) < 2e-3) | (jnp.abs(dw) < 2e-3)
    assert bool(jnp.all(~differs | near_zero))


def test_seeding_reduces_iterations():
    """Paper Tables 1/3: warm-started folds need fewer SMO iterations.

    Uses the adult-like set (mixed bounded/free SVs): on the chance-level
    degenerate sets a SINGLE fold transition's count is seed-order sensitive
    (±20%, see EXPERIMENTS.md §Paper-validation caveat) — full-CV totals for
    those are covered by tests/test_system.py::test_claim2_fewer_iterations."""
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup("adult", n=600, k=6)
    nn = chunks.size
    mask1 = jnp.ones(nn, bool).at[jnp.asarray(chunks[1])].set(False)
    cold = smo_solve(K, y, mask1, ds.C, jnp.zeros(nn), -y)
    alpha0 = seeding.sir_seed(K, y, ds.C, res0, S, R, T)
    warm = smo_solve(K, y, mask1, ds.C, alpha0, init_f(K, y, alpha0))
    assert int(warm.n_iter) < int(cold.n_iter)


def test_full_cv_accuracy_identical():
    ds = make_dataset("madelon", n_override=300)
    rep_cold = run_cv(ds, k=5, method="cold")
    for method in ("sir", "mir"):
        rep = run_cv(ds, k=5, method=method)
        assert rep.accuracy == pytest.approx(rep_cold.accuracy, abs=0.02)


def test_straggler_policy_best_available():
    ds = make_dataset("heart", n_override=150)
    rep = run_cv(ds, k=5, method="sir", straggler_policy="best_available",
                 unavailable_folds=frozenset({1}))
    # fold 2 cannot seed from fold 1 (simulated straggler) -> seeds from 0
    assert rep.folds[2].seed_from == 0
    rep_cold = run_cv(ds, k=5, method="cold")
    assert rep.accuracy == pytest.approx(rep_cold.accuracy, abs=0.02)


# ------------------------------------------------------------- LOO seeds ---

@pytest.mark.parametrize("fn", [seeding.avg_seed_loo, seeding.top_seed_loo])
def test_loo_seed_constraints(fn):
    ds = make_dataset("heart", n_override=100)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    K = kernel_matrix(X, X, gamma=ds.gamma)
    n = 100
    full = smo_solve(K, y, jnp.ones(n, bool), ds.C, jnp.zeros(n), -y)
    for t in [0, 13, 99]:
        a0 = fn(K, y, ds.C, full.alpha, jnp.asarray(t))
        assert float(a0[t]) == 0.0
        assert float(jnp.abs(jnp.sum(a0 * y))) < 1e-6 * ds.C
        assert bool(jnp.all((a0 >= 0) & (a0 <= ds.C)))


def test_kfold_chunks_indices_stay_in_sliced_range():
    """k not dividing n: chunk indices must index the TRUNCATED arrays.
    (The old permutation(n)[:k*m] kept indices >= k*m; jax's clamping
    scatter then silently corrupted that fold's train mask.)"""
    from repro.data.svm_suite import kfold_chunks
    for n, k in [(100, 3), (101, 10), (270, 7)]:
        chunks = kfold_chunks(n, k, seed=0)
        assert chunks.shape == (k, n // k)
        assert int(chunks.max()) < chunks.size
        assert len(np.unique(chunks)) == chunks.size
    # full-CV drive through the non-divisible path (used to crash / corrupt)
    ds = make_dataset("heart", n_override=100)
    rep = run_cv(ds, k=3, method="sir")
    assert all(f.converged for f in rep.folds)


# ---------------------------------------- constraint-repair edge cases -----
# the corners seeding.py documents: label-skewed folds where -s_S is outside
# T's box-feasible range (stage 2 spills into S), and the empty-free-set
# bias fallback.

def test_water_fill_clamps_infeasible_target():
    y = jnp.asarray([1.0, 1.0, -1.0])
    C = 2.0
    lo = jnp.where(y > 0, 0.0, -C)
    hi = jnp.where(y > 0, C, 0.0)
    beta = jnp.asarray([0.5, 1.0, -0.5])
    # target above sum(hi)=4: every coordinate pins to hi
    out = seeding.water_fill(beta, lo, hi, jnp.asarray(100.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(hi), atol=1e-9)
    # target below sum(lo)=-2: every coordinate pins to lo
    out = seeding.water_fill(beta, lo, hi, jnp.asarray(-100.0))
    np.testing.assert_allclose(np.asarray(out), np.asarray(lo), atol=1e-9)


def test_repair_equality_label_skewed_spills_into_S():
    """All-one-label T chunk: -s_S is infeasible for T's box, so stage 2
    must rebalance S itself (the documented corner case)."""
    C = 2.0
    # S: six +1 instances carrying beta=1.5 each (s_S = 9); T: three +1
    # instances — T's box sum range is [0, 6], so the target -9 is infeasible
    y = jnp.asarray([1.0] * 6 + [1.0] * 3 + [-1.0])
    alpha0 = jnp.asarray([1.5] * 6 + [0.0] * 3 + [0.7])
    S_idx = jnp.arange(6)
    T_idx = jnp.arange(6, 9)
    out = seeding.repair_equality(alpha0, y, C, S_idx, T_idx)
    train = jnp.concatenate([S_idx, T_idx])
    assert float(jnp.abs(jnp.sum((y * out)[train]))) < 1e-9
    assert bool(jnp.all((out[train] >= -1e-12) & (out[train] <= C + 1e-12)))
    # the R instance (index 9) is untouched by repair
    assert float(out[9]) == pytest.approx(0.7)


def test_repair_equality_feasible_is_noop_on_S():
    """When T can absorb -s_S, S must not be disturbed (paper: touch T
    first, spill into S only in the infeasible corner)."""
    C = 4.0
    y = jnp.asarray([1.0, -1.0, 1.0, -1.0, 1.0, -1.0])
    alpha0 = jnp.asarray([1.0, 2.0, 0.0, 0.0, 0.5, 0.2])
    S_idx = jnp.asarray([0, 1])
    T_idx = jnp.asarray([2, 3])
    out = seeding.repair_equality(alpha0, y, C, S_idx, T_idx)
    np.testing.assert_allclose(np.asarray(out[S_idx]),
                               np.asarray(alpha0[S_idx]), atol=1e-9)
    assert float(jnp.sum((y * out)[jnp.asarray([0, 1, 2, 3])])) == \
        pytest.approx(0.0, abs=1e-9)


def test_bias_fallback_empty_free_set():
    """With every alpha at a bound the free-set mean is undefined; _bias
    must fall back to the midpoint of (b_up, b_low)."""
    from repro.svm.smo import SMOResult
    n = 6
    y = jnp.asarray([1.0, -1.0] * 3)
    C = 1.0
    alpha = jnp.asarray([1.0, 1.0, 0.0, 0.0, 1.0, 1.0])  # all at 0 or C
    prev = SMOResult(alpha=alpha, f=jnp.linspace(-1, 1, n),
                     n_iter=jnp.asarray(0), converged=jnp.asarray(True),
                     b_up=jnp.asarray(-0.25), b_low=jnp.asarray(0.75))
    mask = jnp.ones(n, bool)
    b = seeding._bias(prev, y, mask, C)
    assert float(b) == pytest.approx(0.5 * (-0.25 + 0.75))
    # and the seeders still produce feasible alpha0 from such a solution
    S_idx = jnp.asarray([0, 1])
    R_idx = jnp.asarray([2, 3])
    T_idx = jnp.asarray([4, 5])
    K = jnp.eye(n)
    a0 = seeding.mir_seed(K, y, C, prev, S_idx, R_idx, T_idx)
    train = jnp.concatenate([S_idx, T_idx])
    assert float(jnp.abs(jnp.sum((y * a0)[train]))) < 1e-9
    assert bool(jnp.all((a0 >= -1e-12) & (a0 <= C + 1e-12)))
    assert float(jnp.abs(a0[R_idx]).max()) == 0.0


def test_scale_seed_C_constraints():
    """C-grid transition seed: box at the NEW C, exact equality, zero off
    the training mask."""
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup("heart", n=200, k=5)
    nn = chunks.size
    mask0 = jnp.ones(nn, bool).at[jnp.asarray(chunks[0])].set(False)
    for C_new in (ds.C / 8.0, ds.C * 8.0):
        a0 = seeding.scale_seed_C(res0.alpha, y, ds.C, C_new, mask0)
        assert bool(jnp.all((a0 >= -1e-12) & (a0 <= C_new + 1e-12)))
        assert float(jnp.abs(jnp.sum(a0 * y))) < 1e-6 * max(C_new, 1.0)
        assert float(jnp.abs(jnp.where(mask0, 0.0, a0)).max()) == 0.0


# ----------------------------------------------------- jittable ATO -------
# ato_seed is a fixed-shape lax.while_loop (bordered KKT solve over a padded
# working set); ato_seed_ref is the eager paper-faithful loop it replaced.
# The parity contract: feasible seed, alpha0 close up to the repair
# tolerance, and — the real claim — the seeded solve reaching the same fixed
# point with comparable iteration counts.

ATO_SUITE_N = {"adult": 400, "heart": 270, "madelon": 400, "mnist": 400,
               "webdata": 400}


@pytest.mark.parametrize("name", sorted(ATO_SUITE_N))
def test_ato_jit_parity_suite(name):
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup(name, n=ATO_SUITE_N[name],
                                                    k=5)
    a_ref = seeding.ato_seed_ref(K, y, ds.C, res0, S, R, T)
    a_jit = seeding.ato_seed(K, y, ds.C, res0, S, R, T)
    eps = 1e-8 * max(ds.C, 1.0)
    assert bool(jnp.all((a_jit >= -eps) & (a_jit <= ds.C + eps)))
    assert float(jnp.abs(jnp.sum(a_jit * y))) < 1e-6 * max(ds.C, 1.0)
    assert float(jnp.abs(a_jit[R]).max()) == 0.0
    # bordered KKT vs pinv least squares: same ramp, slightly different
    # Phi per step (heart's full 30-step ramp accumulates the most)
    assert float(jnp.max(jnp.abs(a_jit - a_ref))) < 0.2 * ds.C
    nn = chunks.size
    mask1 = jnp.ones(nn, bool).at[jnp.asarray(chunks[1])].set(False)
    warm_ref = smo_solve(K, y, mask1, ds.C, a_ref, init_f(K, y, a_ref))
    warm_jit = smo_solve(K, y, mask1, ds.C, a_jit, init_f(K, y, a_jit))
    assert bool(warm_jit.converged)
    from repro.svm import dual_objective
    assert float(dual_objective(K, y, warm_jit.alpha)) == pytest.approx(
        float(dual_objective(K, y, warm_ref.alpha)), rel=1e-3, abs=1e-6)
    # comparable warm-start quality (not bit-identical trajectories)
    assert int(warm_jit.n_iter) <= 1.5 * int(warm_ref.n_iter) + 300


def test_ato_jit_empty_free_set():
    """All-bounded prev solution: the masked solve must degrade to the pure
    T/R ramp (Phi = 0), matching the reference's M-empty branch exactly."""
    from repro.svm.smo import SMOResult
    n = 8
    y = jnp.asarray([1.0, -1.0] * 4)
    C = 1.0
    alpha = jnp.asarray([1.0, 1.0, 0.0, 0.0, 1.0, 1.0, 0.0, 0.0])
    K = jnp.eye(n) + 0.05
    f = init_f(K, y, alpha)
    prev = SMOResult(alpha=alpha, f=f, n_iter=jnp.asarray(0),
                     converged=jnp.asarray(True), b_up=jnp.asarray(-0.25),
                     b_low=jnp.asarray(0.75))
    S_idx = jnp.asarray([0, 1, 2, 3])
    R_idx = jnp.asarray([4, 5])
    T_idx = jnp.asarray([6, 7])
    a_ref = seeding.ato_seed_ref(K, y, C, prev, S_idx, R_idx, T_idx)
    a_jit = seeding.ato_seed(K, y, C, prev, S_idx, R_idx, T_idx)
    np.testing.assert_allclose(np.asarray(a_jit), np.asarray(a_ref),
                               atol=1e-9)
    train = jnp.concatenate([S_idx, T_idx])
    assert float(jnp.abs(jnp.sum((y * a_jit)[train]))) < 1e-9
    assert float(jnp.abs(a_jit[R_idx]).max()) == 0.0


def test_ato_jit_drained_R_exits_early():
    """alpha_R already zero: R_active is empty from step 0; the loop still
    ramps T and terminates via the eta=1 exit, like the reference."""
    from repro.svm.smo import SMOResult
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup("heart", n=150, k=5)
    alpha = res0.alpha.at[R].set(0.0)
    prev = SMOResult(alpha=alpha, f=init_f(K, y, alpha), n_iter=res0.n_iter,
                     converged=res0.converged, b_up=res0.b_up,
                     b_low=res0.b_low)
    a_ref = seeding.ato_seed_ref(K, y, ds.C, prev, S, R, T)
    a_jit = seeding.ato_seed(K, y, ds.C, prev, S, R, T)
    eps = 1e-8 * max(ds.C, 1.0)
    assert bool(jnp.all((a_jit >= -eps) & (a_jit <= ds.C + eps)))
    assert float(jnp.abs(jnp.sum(a_jit * y))) < 1e-6 * max(ds.C, 1.0)
    assert float(jnp.abs(a_jit[R]).max()) == 0.0
    assert float(jnp.max(jnp.abs(a_jit - a_ref))) < 0.2 * ds.C


def test_ato_coupled_working_set(monkeypatch):
    """A free set wider than ATO_MAX_M (adult at its published size keeps
    ~90% of its rows free) takes the coupled selection, here over f32
    kernel values as on the chip: the seed meets the box and equality
    constraints, and the fold it starts converges to the cold solve's
    held-out predictions."""
    from repro.svm import bias_from_solution, decision_function
    ds, K, y, chunks, res0, (S, R, T) = _fold_setup("adult", n=600, k=6)
    monkeypatch.setattr(seeding, "ATO_MAX_M", 128)
    coupled = []
    ato_jit = seeding._ato_seed_jit

    def spy(*args, **kw):
        coupled.append(kw["coupled"])
        return ato_jit(*args, **kw)

    monkeypatch.setattr(seeding, "_ato_seed_jit", spy)
    K32 = K.astype(jnp.float32)
    alpha0 = seeding.ato_seed(K32, y, ds.C, res0, S, R, T)
    assert coupled == [True]
    eps = 1e-8 * max(ds.C, 1.0)
    assert bool(jnp.all((alpha0 >= -eps) & (alpha0 <= ds.C + eps)))
    assert float(jnp.abs(jnp.sum(alpha0 * y))) < 1e-6 * max(ds.C, 1.0)
    assert float(jnp.abs(alpha0[R]).max()) == 0.0
    nn = chunks.size
    mask1 = jnp.ones(nn, bool).at[jnp.asarray(chunks[1])].set(False)
    cold = smo_solve(K32, y, mask1, ds.C, jnp.zeros(nn), -y)
    warm = smo_solve(K32, y, mask1, ds.C, alpha0, init_f(K32, y, alpha0))
    assert bool(warm.converged)
    t_idx = jnp.asarray(chunks[1])
    dc = decision_function(K32[t_idx], y, cold.alpha,
                           bias_from_solution(cold, y, mask1, ds.C))
    dw = decision_function(K32[t_idx], y, warm.alpha,
                           bias_from_solution(warm, y, mask1, ds.C))
    differs = (dc >= 0) != (dw >= 0)
    near_zero = (jnp.abs(dc) < 2e-3) | (jnp.abs(dw) < 2e-3)
    assert bool(jnp.all(~differs | near_zero))


# ------------------------------------------------------ property tests -----

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.5, 100.0))
def test_water_fill_property(seed, C):
    """water_fill returns values in the box whose sum hits any feasible
    target (the paper's AdjustAlpha invariant)."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(3, 40))
    y = jnp.asarray(np.where(rng.random(m) < 0.5, 1.0, -1.0))
    beta = jnp.asarray(rng.uniform(-C, C, m)) * (y > 0) \
        + jnp.asarray(rng.uniform(-C, 0, m)) * (y < 0)
    lo = jnp.where(y > 0, 0.0, -C)
    hi = jnp.where(y > 0, C, 0.0)
    target = float(rng.uniform(float(jnp.sum(lo)), float(jnp.sum(hi))))
    out = seeding.water_fill(jnp.clip(beta, lo, hi), lo, hi,
                             jnp.asarray(target))
    assert bool(jnp.all((out >= lo - 1e-9) & (out <= hi + 1e-9)))
    assert float(jnp.sum(out)) == pytest.approx(target, abs=1e-6 * max(C, 1))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_smo_invariants_random_problems(seed):
    """Random tiny SVMs: the solver always returns a feasible, converged
    dual within the iteration budget."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(10, 60))
    d = int(rng.integers(2, 8))
    X = jnp.asarray(rng.normal(size=(n, d)))
    y = jnp.asarray(np.where(rng.random(n) < 0.5, 1.0, -1.0))
    if float(jnp.abs(y).sum()) == float(jnp.abs(y.sum())):
        return  # single-class sample: SVM undefined
    K = kernel_matrix(X, X, gamma=0.5)
    res = smo_solve(K, y, jnp.ones(n, bool), C_TEST, jnp.zeros(n), -y,
                    max_iter=200_000)
    assert bool(res.converged)
    assert float(jnp.abs(jnp.sum(res.alpha * y))) < 1e-8
    assert bool(jnp.all((res.alpha >= 0) & (res.alpha <= C_TEST)))
