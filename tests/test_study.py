"""Study API / multi-source lane pool: per-lane bit-parity with the
single-source sequential path across schedule shapes and mixed gamma
sources, mid-study kill/resume under a different schedule, plan-built
LOO/grid parity, the seed-transform registry, and the SVC facade."""
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core import seeding
from repro.core.cv import _fold_masks, _transition_idx, run_loo
from repro.core.study import Plan, StudyCheckpoint, run_plan
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (DenseKernel, LanePool, init_f, kernel_matrix,
                       smo_solve)
from repro.svm.precision import kernel_input

SUITE = ("adult", "heart", "madelon", "mnist", "webdata")
GAMMA_SCALES = (0.5, 2.0)   # two sources per dataset: gamma/2 and 2*gamma


def _setup(name, n=120, k=4):
    ds = make_dataset(name, n_override=n)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    chunks = kfold_chunks(n, k, seed=0)
    nn = chunks.size
    Ks = [kernel_matrix(X, X, gamma=s * ds.gamma)[:nn][:, :nn]
          for s in GAMMA_SCALES]
    return ds, Ks, y[:nn], chunks, jnp.asarray(_fold_masks(chunks))


@pytest.mark.parametrize("max_width", [0, 1, 3])
@pytest.mark.parametrize("name", SUITE)
def test_pool_multi_source_parity_bitwise(name, max_width):
    """Lanes spread over two gamma sources, driven through one pool with
    tiny chunks (many forced repack boundaries), must be bit-identical to
    sequential single-source solves on every suite dataset, for every
    schedule shape: unbounded packing, pure width-1 round-robin (the CPU
    cost-model default), and a capped width that parks/rotates lanes
    across sources."""
    ds, (K0, K1), y, chunks, masks = _setup(name)
    n = y.shape[0]
    pool = LanePool({"g0": DenseKernel(K0), "g1": DenseKernel(K1)}, y,
                    chunk_iters=64, lane_quantum=2, max_width=max_width)
    for h in range(3):
        for key in ("g0", "g1"):
            pool.add((key, h), masks[h], ds.C, jnp.zeros(n, K0.dtype), -y,
                     source=key)
    results = pool.run()
    for key, K in (("g0", K0), ("g1", K1)):
        for h in range(3):
            seq = smo_solve(K, y, masks[h], ds.C, jnp.zeros(n), -y)
            got = results[(key, h)]
            np.testing.assert_array_equal(np.asarray(seq.alpha),
                                          np.asarray(got.alpha))
            np.testing.assert_array_equal(np.asarray(seq.f),
                                          np.asarray(got.f))
            assert int(seq.n_iter) == int(got.n_iter)
            assert bool(seq.converged) == bool(got.converged)
    occ = pool.occupancy
    assert set(occ["per_source"]) == {"g0", "g1"}
    if max_width:
        assert occ["peak_width"] <= 2 * max_width  # <= cap per chunk, summed
    else:
        assert occ["peak_width"] >= 4


def test_pool_cross_source_dependency():
    """A lane in one source seeded from a lane in ANOTHER source (admission
    crosses kernel sources) reproduces the eagerly-seeded solve exactly."""
    ds, (K0, K1), y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"g0": DenseKernel(K0), "g1": DenseKernel(K1)}, y,
                    chunk_iters=64, max_width=0)
    pool.add("a", masks[0], ds.C, jnp.zeros(n, K0.dtype), -y, source="g0")

    def seed_fn(prev):
        a0 = seeding.scale_seed_C(prev.alpha, y, ds.C, 2 * ds.C, masks[0])
        return a0, init_f(K1, y, a0)
    pool.add("b", masks[0], 2 * ds.C, source="g1", dep="a", seed_fn=seed_fn)
    results = pool.run()

    ref_a = smo_solve(K0, y, masks[0], ds.C, jnp.zeros(n), -y)
    a0 = seeding.scale_seed_C(ref_a.alpha, y, ds.C, 2 * ds.C, masks[0])
    ref_b = smo_solve(K1, y, masks[0], 2 * ds.C, a0, init_f(K1, y, a0))
    np.testing.assert_array_equal(np.asarray(ref_b.alpha),
                                  np.asarray(results["b"].alpha))
    assert int(ref_b.n_iter) == int(results["b"].n_iter)


def test_pool_after_ordering_edge():
    """An ``after`` edge holds an explicitly-started lane until the target
    retires, without touching its start point."""
    ds, (K0, _), y, chunks, masks = _setup("heart")
    n = y.shape[0]
    pool = LanePool({"g0": DenseKernel(K0)}, y, chunk_iters=64)
    order = []
    pool.on_result = lambda lid, res: order.append(lid)
    pool.add("first", masks[0], ds.C, jnp.zeros(n, K0.dtype), -y)
    pool.add("second", masks[1], ds.C, jnp.zeros(n, K0.dtype), -y,
             after="first")
    results = pool.run()
    assert order == ["first", "second"]
    seq = smo_solve(K0, y, masks[1], ds.C, jnp.zeros(n), -y)
    np.testing.assert_array_equal(np.asarray(seq.alpha),
                                  np.asarray(results["second"].alpha))


def _grid_style_plan(Ks, y, masks, chunks, C, max_width=0):
    """A small two-source plan with fold-chain dependencies and tuple lane
    ids — the shape the grid driver builds."""
    plan = Plan(sources={0: DenseKernel(Ks[0]), 1: DenseKernel(Ks[1])}, y=y,
                chunk_iters=64, lane_quantum=2, max_width=max_width)
    n = y.shape[0]
    for gi in (0, 1):
        plan.lane((gi, 0), source=gi, train_mask=masks[0], C=C,
                  alpha0=jnp.zeros(n), f0=-y)
        for h in (1, 2):
            S, R, T = _transition_idx(chunks, h - 1, h)
            plan.lane((gi, h), source=gi, train_mask=masks[h], C=C,
                      dep=(gi, h - 1), transform="fold",
                      params=dict(method="sir", S_idx=S, R_idx=R, T_idx=T))
        for h in range(3):
            plan.evaluate((gi, h), chunks[h])
    return plan


def test_run_plan_kill_resume_different_schedule(tmp_path):
    """Kill a checkpointed study mid-flight; resume under a DIFFERENT
    schedule shape (width-1 round-robin vs unbounded) and with tuple lane
    ids (the JSON round-trip case). Every lane must land on the identical
    result, and the restored-done lanes must be flagged."""
    ds, Ks, y, chunks, masks = _setup("heart")
    full = run_plan(_grid_style_plan(Ks, y, masks, chunks, ds.C))

    mgr = CheckpointManager(str(tmp_path / "study"), max_to_keep=1000)
    ck = StudyCheckpoint(manager=mgr, meta={"k": 3, "dataset": "heart"})
    run_plan(_grid_style_plan(Ks, y, masks, chunks, ds.C), checkpoint=ck)
    steps = mgr.steps_of_class("study")
    assert len(steps) >= 6
    # 'crash' two-thirds in: by then the fold-chain heads have retired, so
    # the surviving snapshot holds BOTH done lanes (restored as results)
    # and live mid-flight lanes (resumed mid-sequence)
    for s in steps[2 * len(steps) // 3:]:
        shutil.rmtree(mgr._step_dir(s))

    mgr2 = CheckpointManager(str(tmp_path / "study"), max_to_keep=1000)
    ck2 = StudyCheckpoint(manager=mgr2, meta={"k": 3, "dataset": "heart"})
    resumed = run_plan(_grid_style_plan(Ks, y, masks, chunks, ds.C,
                                        max_width=1), checkpoint=ck2)
    for lid, res in full.results.items():
        np.testing.assert_array_equal(np.asarray(res.alpha),
                                      np.asarray(resumed.results[lid].alpha))
        assert full.stats[lid].n_iter == resumed.stats[lid].n_iter
        assert full.evals[lid] == resumed.evals[lid]
    assert any(st.restored for st in resumed.stats.values())

    # a different plan identity must be rejected, not silently resumed
    mgr3 = CheckpointManager(str(tmp_path / "study"), max_to_keep=1000)
    ck3 = StudyCheckpoint(manager=mgr3, meta={"k": 4, "dataset": "heart"})
    with pytest.raises(ValueError, match="cannot resume"):
        run_plan(_grid_style_plan(Ks, y, masks, chunks, ds.C),
                 checkpoint=ck3)


def test_run_plan_streams_results():
    """on_result fires once per solved lane, at retirement, with the final
    result object — long studies consume lanes as they land."""
    ds, Ks, y, chunks, masks = _setup("heart")
    seen = {}
    sres = run_plan(_grid_style_plan(Ks, y, masks, chunks, ds.C),
                    on_result=lambda lid, res: seen.setdefault(lid, res))
    assert set(seen) == set(sres.results)
    for lid, res in seen.items():
        assert res is sres.results[lid]


def test_plan_wire_roundtrip_zoo():
    """``plan_to_dict`` -> real JSON -> ``plan_from_dict`` over this
    file's plan shapes: the round-tripped grid plan must EXECUTE
    bit-identically, every zoo member must be a serialization fixed
    point, and hostile wire images die at parse time with named errors
    (the daemon's first line of defense — before the analyzer runs)."""
    import copy
    import json

    from repro.core.study import plan_from_dict, plan_to_dict
    from repro.svm.sources import KernelSpec

    ds, Ks, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    X = jnp.asarray(ds.X)[:n]
    zoo = [_grid_style_plan(Ks, y, masks, chunks, ds.C),
           _grid_style_plan(Ks, y, masks, chunks, ds.C, max_width=1)]
    spec_plan = Plan(sources={"s": KernelSpec(X=X, gamma=ds.gamma, n=n)},
                     y=y, chunk_iters=64, max_resident=1, cache_bytes=1 << 30)
    spec_plan.lane("a", train_mask=masks[0], C=ds.C,
                   alpha0=jnp.zeros(n), f0=-y)
    spec_plan.lane("b", train_mask=masks[0], C=2 * ds.C, dep="a",
                   transform="scale_C",
                   params=dict(C_old=ds.C, train_mask=masks[0]),
                   after="a")
    spec_plan.evaluate("a", chunks[0])
    zoo.append(spec_plan)
    pallas_plan = Plan(sources={0: KernelSpec(X=X, gamma=ds.gamma, n=n)},
                       y=y, wss="1", source_backend="pallas_rbf")
    pallas_plan.lane(0, train_mask=masks[0], C=ds.C,
                     alpha0=jnp.zeros(n), f0=-y)
    zoo.append(pallas_plan)

    for plan in zoo:
        d = json.loads(json.dumps(plan_to_dict(plan)))
        back = plan_from_dict(d)
        # fixed point: re-serializing the parsed plan is byte-stable
        assert json.loads(json.dumps(plan_to_dict(back))) == d

    solo = run_plan(zoo[0])
    wired = run_plan(plan_from_dict(
        json.loads(json.dumps(plan_to_dict(zoo[0])))))
    assert set(solo.results) == set(wired.results)
    for lid, res in solo.results.items():
        np.testing.assert_array_equal(np.asarray(res.alpha),
                                      np.asarray(wired.results[lid].alpha))
        np.testing.assert_array_equal(np.asarray(res.f),
                                      np.asarray(wired.results[lid].f))
        assert int(res.n_iter) == int(wired.results[lid].n_iter)
    assert solo.evals == wired.evals

    # parse-time hardening: hostile images name their defect
    good = plan_to_dict(zoo[0])
    bad = copy.deepcopy(good)
    bad["lanes"][1]["transform"] = "exfiltrate"
    with pytest.raises(ValueError, match="unknown transform 'exfiltrate'"):
        plan_from_dict(bad)
    good_spec = plan_to_dict(spec_plan)
    bad = copy.deepcopy(good_spec)
    bad["sources"][0][1]["kind"] = "poly"
    with pytest.raises(ValueError, match="unknown source kind 'poly'"):
        plan_from_dict(bad)
    bad = copy.deepcopy(good)
    bad["lanes"][0]["C"] = float("inf")
    with pytest.raises(ValueError, match="non-finite"):
        plan_from_dict(bad)
    bad = copy.deepcopy(good)
    bad["tol"] = float("nan")
    with pytest.raises(ValueError, match="non-finite"):
        plan_from_dict(bad)
    bad = copy.deepcopy(good)
    del bad["__plan__"]
    with pytest.raises(ValueError, match="not a wire plan"):
        plan_from_dict(bad)


def test_transform_registry_matches_seeders():
    """The named transforms reproduce their underlying seeders exactly."""
    ds, (K, _), y, chunks, masks = _setup("heart")
    prev = smo_solve(K, y, masks[0], ds.C, jnp.zeros(y.shape[0]), -y)
    S, R, T = _transition_idx(chunks, 0, 1)
    for method in ("sir", "mir", "ato"):
        direct = seeding.SEEDERS[method](K, y, ds.C, prev, S, R, T)
        named = seeding.TRANSFORMS["fold"](K, y, ds.C, prev, method=method,
                                           S_idx=S, R_idx=R, T_idx=T)
        np.testing.assert_array_equal(np.asarray(direct), np.asarray(named))
    sc = seeding.TRANSFORMS["scale_C"](K, y, 2 * ds.C, prev, C_old=ds.C,
                                       train_mask=masks[0])
    np.testing.assert_array_equal(
        np.asarray(sc),
        np.asarray(seeding.scale_seed_C(prev.alpha, y, ds.C, 2 * ds.C,
                                        masks[0])))
    assert {"fold", "scale_C", "loo_avg", "loo_top"} <= set(seeding.TRANSFORMS)


def test_run_plan_rejects_bad_specs():
    ds, (K, _), y, chunks, masks = _setup("heart")
    n = y.shape[0]
    plan = Plan(sources={"s": DenseKernel(K)}, y=y)
    plan.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
    plan.lane(0, train_mask=masks[1], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
    with pytest.raises(ValueError, match="duplicate"):
        run_plan(plan)
    plan2 = Plan(sources={"s": DenseKernel(K)}, y=y)
    plan2.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
    plan2.lane(1, train_mask=masks[1], C=ds.C, dep=0, transform="nope")
    with pytest.raises(ValueError, match="unknown transform"):
        run_plan(plan2)


def test_validate_plan_cycle_names_the_cycle():
    """A dep/after cycle is reported AS the cycle — every offending lane
    by name, not a drain-time 'pending lanes' dump."""
    ds, (K, _), y, chunks, masks = _setup("heart")
    n = y.shape[0]
    plan = Plan(sources={"s": DenseKernel(K)}, y=y)
    plan.lane("a", train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y,
              after="b")
    plan.lane("b", train_mask=masks[1], C=ds.C, alpha0=jnp.zeros(n), f0=-y,
              after="a")
    with pytest.raises(ValueError, match=r"cycle.*'.' -> '.' -> '.'"):
        run_plan(plan)


def test_validate_plan_dense_k_names_lane_and_source():
    """A seed transform on a K-less source fails at entry, naming both
    the lane and the source key it resolved to."""
    from repro.svm import PallasRBF
    ds, _, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    X = jnp.asarray(ds.X)[:n]
    plan = Plan(sources={"rbf": PallasRBF(X, ds.gamma)}, y=y, wss="1")
    plan.lane("w0", train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
    plan.lane("w1", train_mask=masks[1], C=ds.C, dep="w0", transform="fold",
              params=dict(method="sir",
                          S_idx=jnp.arange(4), R_idx=jnp.arange(4),
                          T_idx=jnp.arange(4)))
    with pytest.raises(ValueError, match=r"'w1'.*'fold'.*'rbf' has no K"):
        run_plan(plan)


def test_bad_source_backend_fails_at_entry():
    """A typo'd ``source_backend`` is rejected before any source could
    materialize — on the Plan (via run_plan) and at run_grid's entry."""
    from repro.core.grid import run_grid
    from repro.svm.sources import KernelSpec

    class ExplodingSpec(KernelSpec):
        def materialize(self):
            raise AssertionError("materialized during entry validation")

    ds, _, y, chunks, masks = _setup("heart")
    n = y.shape[0]
    spec = ExplodingSpec(X=jnp.asarray(ds.X), gamma=ds.gamma, n=n)
    plan = Plan(sources={0: spec}, y=y, source_backend="pallas_rbt")
    plan.lane(0, source=0, train_mask=masks[0], C=ds.C,
              alpha0=jnp.zeros(n), f0=-y)
    with pytest.raises(ValueError, match="unknown source_backend"):
        run_plan(plan)
    with pytest.raises(ValueError, match="unknown source_backend"):
        run_grid(ds, [ds.C], [ds.gamma], k=3, source_backend="dence")


# ----------------------------------------------------------------- run_loo

def _loo_reference(ds, method, rounds, tol=1e-3, max_iter=2_000_000):
    """The pre-Study sequential LOO loop, kept inline as the parity oracle
    for the plan-built ``run_loo``, under the entry points' precision policy
    (f32 kernel values, f64 state)."""
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    n = ds.n
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)
    full = smo_solve(K, y, jnp.ones(n, bool), ds.C, jnp.zeros(n, K.dtype),
                     -y, tol=tol, max_iter=max_iter)
    from repro.svm import bias_from_solution, predict
    total_iters, correct = 0, 0
    prev, prev_t = full, None
    for t in range(rounds):
        t_j = jnp.asarray(t)
        mask = jnp.ones(n, bool).at[t_j].set(False)
        if method == "cold":
            alpha0, f0 = jnp.zeros(n, K.dtype), -y
        elif method in ("avg", "top"):
            fn = (seeding.avg_seed_loo if method == "avg"
                  else seeding.top_seed_loo)
            alpha0 = fn(K, y, ds.C, full.alpha, t_j)
            f0 = init_f(K, y, alpha0)
        else:
            if prev_t is None:
                alpha0 = seeding.avg_seed_loo(K, y, ds.C, full.alpha, t_j)
            else:
                S = jnp.asarray(np.delete(np.arange(n), [prev_t, t]))
                alpha0 = seeding.SEEDERS[method](
                    K, y, ds.C, prev, S, jnp.asarray([t]),
                    jnp.asarray([prev_t]))
            f0 = init_f(K, y, alpha0)
        res = smo_solve(K, y, mask, ds.C, alpha0, f0, tol=tol,
                        max_iter=max_iter)
        total_iters += int(res.n_iter)
        b = bias_from_solution(res, y, mask, ds.C)
        pred = predict(K[t_j][None, :], y, res.alpha, b)
        correct += int(pred[0] == y[t_j])
        prev, prev_t = res, t
    return {"base_iterations": int(full.n_iter), "iterations": total_iters,
            "accuracy": round(correct / rounds, 4)}


@pytest.mark.parametrize("method", ["sir", "avg", "cold"])
def test_run_loo_plan_matches_sequential_reference(method):
    """The plan-built LOO (chain deps for SIR, fan-out for AVG, independent
    lanes for cold) reproduces the sequential protocol's iteration counts
    and accuracy exactly."""
    ds = make_dataset("heart", n_override=80)
    got = run_loo(ds, method=method, rounds=6)
    ref = _loo_reference(ds, method, rounds=6)
    assert got["base_iterations"] == ref["base_iterations"]
    assert got["iterations"] == ref["iterations"]
    assert got["accuracy"] == ref["accuracy"]


def test_run_loo_kill_resume(tmp_path):
    """run_loo through the plan builder gets mid-study checkpoint/resume:
    kill after a few chunks, resume, and the report is identical."""
    ds = make_dataset("heart", n_override=80)
    full = run_loo(ds, method="sir", rounds=5, chunk_iters=64)

    mgr = CheckpointManager(str(tmp_path / "loo"), max_to_keep=1000)
    run_loo(ds, method="sir", rounds=5, chunk_iters=64,
            checkpoint_manager=mgr)
    steps = mgr.steps_of_class("study")
    assert len(steps) >= 3
    for s in steps[3:]:
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "loo"), max_to_keep=1000)
    resumed = run_loo(ds, method="sir", rounds=5, chunk_iters=64,
                      checkpoint_manager=mgr2)
    for key in ("base_iterations", "iterations", "accuracy", "rounds"):
        assert resumed[key] == full[key]
    # a different protocol is a different study: reject, don't mix
    mgr3 = CheckpointManager(str(tmp_path / "loo"), max_to_keep=1000)
    with pytest.raises(ValueError, match="cannot resume"):
        run_loo(ds, method="mir", rounds=5, chunk_iters=64,
                checkpoint_manager=mgr3)


# ---------------------------------------------------------------- run_grid

@pytest.mark.parametrize("name", SUITE)
def test_run_grid_pooled_matches_per_row(name):
    """The cross-gamma pooled grid must be bit-identical (per-cell
    iteration counts AND accuracies) to one single-gamma grid per row on
    every suite dataset."""
    from repro.core.grid import run_grid
    ds = make_dataset(name, n_override=100)
    gammas = [0.5 * ds.gamma, 2 * ds.gamma]
    kw = dict(Cs=[ds.C, 4 * ds.C], k=3, method="sir", chunk_iters=256)
    pooled = run_grid(ds, gammas=gammas, **kw)
    rows = [c for g in gammas for c in run_grid(ds, gammas=[g], **kw).cells]
    assert [(c.C, c.gamma, c.iterations, c.acc_correct, c.converged)
            for c in pooled.cells] == \
        [(c.C, c.gamma, c.iterations, c.acc_correct, c.converged)
         for c in rows]
    assert set(pooled.occupancy["per_source"]) == {"0", "1"}


def test_run_grid_kill_resume(tmp_path):
    """A killed cross-gamma grid resumes as one study and lands on the
    identical per-cell report."""
    from repro.core.grid import run_grid
    ds = make_dataset("heart", n_override=100)
    kw = dict(Cs=[ds.C, 4 * ds.C], gammas=[0.5 * ds.gamma, 2 * ds.gamma],
              k=3, method="sir", chunk_iters=64)
    full = run_grid(ds, **kw)

    mgr = CheckpointManager(str(tmp_path / "grid"), max_to_keep=1000)
    run_grid(ds, checkpoint_manager=mgr, **kw)
    steps = mgr.steps_of_class("study")
    assert len(steps) >= 3
    for s in steps[3:]:
        shutil.rmtree(mgr._step_dir(s))
    mgr2 = CheckpointManager(str(tmp_path / "grid"), max_to_keep=1000)
    resumed = run_grid(ds, checkpoint_manager=mgr2, **kw)
    assert [(c.iterations, c.acc_correct) for c in resumed.cells] == \
        [(c.iterations, c.acc_correct) for c in full.cells]


# --------------------------------------------------------------------- SVC

def test_svc_fit_predict_separable():
    ds = make_dataset("webdata", n_override=140)   # near-separable regime
    from repro.svm import SVC
    svc = SVC(C=ds.C, gamma=ds.gamma).fit(ds.X, ds.y)
    assert svc.converged_
    assert svc.score(ds.X, ds.y) > 0.95
    pred = svc.predict(ds.X[:7])
    assert set(np.unique(pred)) <= set(svc.classes_)


def test_svc_label_mapping():
    """Arbitrary binary labels round-trip through the ±1 encoding."""
    ds = make_dataset("heart", n_override=80)
    from repro.svm import SVC
    y01 = np.where(ds.y > 0, "pos", "neg")
    svc = SVC(C=ds.C, gamma=ds.gamma).fit(ds.X, y01)
    assert set(np.unique(svc.predict(ds.X))) <= {"pos", "neg"}


def test_svc_cross_validate_matches_run_cv():
    """SVC.cross_validate is the run_cv plan builder on the estimator's
    hyper-parameters — identical per-fold trajectories."""
    from repro.core.cv import run_cv
    from repro.svm import SVC
    ds = make_dataset("heart", n_override=100)
    rep = SVC(C=ds.C, gamma=ds.gamma).cross_validate(ds.X, ds.y, k=4,
                                                     method="sir")
    ref = run_cv(make_dataset("heart", n_override=100), k=4, method="sir")
    assert [f.n_iter for f in rep.folds] == [f.n_iter for f in ref.folds]
    assert rep.accuracy == ref.accuracy
