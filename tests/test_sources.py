"""Kernel-source LRU: compute-on-demand factories, schedule-distance
eviction under a residency budget, bit-parity of budgeted grids, deferred
fused validation, plan validation at entry, and occupancy merging."""
import shutil

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.core.cv import _fold_masks
from repro.core.grid import run_grid
from repro.core.study import Plan, run_plan
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import (DenseKernel, FusedRBF, KernelSpec, LanePool,
                       SourceCache, kernel_matrix, smo_solve)

SUITE = ("adult", "heart", "madelon", "mnist", "webdata")


def _setup(name, n=100, k=3):
    ds = make_dataset(name, n_override=n)
    X = jnp.asarray(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    chunks = kfold_chunks(n, k, seed=0)
    nn = chunks.size
    return ds, X, y[:nn], nn, jnp.asarray(_fold_masks(chunks))


# ------------------------------------------------------------- KernelSpec

def test_kernel_spec_slices_before_kernel_call():
    """The k-fold truncation is applied to X BEFORE the kernel call: the
    materialized matrix is the (n, n) kernel of X[:n], not a slice of the
    full (N, N) matrix (which wastes O(N^2 - n^2) work — the old
    run_grid bug)."""
    ds, X, y, n, _ = _setup("heart", n=100, k=3)   # 100 % 3 != 0 -> n < 100
    assert n < 100
    spec = KernelSpec(X=X, gamma=ds.gamma, n=n)
    src = spec.materialize()
    assert isinstance(src, DenseKernel)
    assert src.K.shape == (n, n)
    np.testing.assert_array_equal(
        np.asarray(src.K),
        np.asarray(kernel_matrix(X[:n], X[:n], gamma=ds.gamma)))
    # the residency half of the protocol answers without materializing
    assert spec.dtype == X.dtype
    assert spec.nbytes == n * n * X.dtype.itemsize
    assert spec.fused is False
    assert src.nbytes == src.K.nbytes == spec.nbytes


def test_source_cache_budget_and_schedule_distance_eviction():
    """max_resident bounds managed residency; the victim is the resident
    source with the FEWEST remaining lanes (schedule distance), never the
    sticky source while another candidate exists; pinned (dense) entries
    never count or evict."""
    ds, X, y, n, _ = _setup("heart")
    specs = {k: KernelSpec(X=X, gamma=g * ds.gamma, n=n)
             for k, g in (("a", 0.5), ("b", 1.0), ("c", 2.0))}
    specs["pin"] = DenseKernel(jnp.eye(n))
    remaining = {"a": 5, "b": 1, "c": 3}
    evicted = []
    cache = SourceCache(specs, max_resident=2,
                        distance=lambda k: remaining[k],
                        sticky=lambda: "a",
                        on_evict=evicted.append)
    assert cache.resident("pin") and not cache.resident("a")
    cache.get("a")
    cache.get("b")
    assert cache.peak_resident == 3          # pin + a + b
    # c forces an eviction: b has the fewest remaining lanes -> victim,
    # even though a is older (schedule distance beats recency); a is also
    # the sticky source and must survive
    cache.get("c")
    assert evicted == ["b"]
    assert cache.resident("a") and cache.resident("c")
    assert not cache.resident("b") and cache.resident("pin")
    # re-materialization is bit-identical (pure function of the spec)
    K_b1 = np.asarray(cache.get("b").K)      # evicts c (distance 3 < a's 5)
    assert evicted == ["b", "c"]
    np.testing.assert_array_equal(K_b1, np.asarray(specs["b"].materialize().K))
    assert cache.materializations == 4 and cache.evictions == 2
    assert cache.stats["peak_resident_bytes"] >= 2 * specs["a"].nbytes


def test_source_cache_byte_budget():
    ds, X, y, n, _ = _setup("heart")
    specs = {g: KernelSpec(X=X, gamma=g * ds.gamma, n=n) for g in (1, 2, 3)}
    one = specs[1].nbytes
    cache = SourceCache(specs, cache_bytes=2 * one + 1)
    cache.get(1), cache.get(2), cache.get(3)
    assert cache.resident_bytes <= 2 * one + 1
    assert cache.peak_resident == 2 and cache.evictions == 1


# --------------------------------------------- pool-level eviction parity

def test_pool_eviction_rematerializes_mid_lane_bitwise():
    """A source's kernel is evicted MID-SOLVE — between a batched group's
    chunks, an external cache reader pulls the OTHER source through a
    1-kernel budget, forcing the serving kernel out (packed states written
    back) and a re-materialization at the next chunk. Every lane still
    lands bit-identical to a solo solve."""
    ds, X, y, n, masks = _setup("heart")
    specs = {"a": KernelSpec(X=X, gamma=0.5 * ds.gamma, n=n),
             "b": KernelSpec(X=X, gamma=2.0 * ds.gamma, n=n)}
    pool = LanePool(specs, y, chunk_iters=64, max_width=0, max_resident=1)
    # two lanes on "a" so its group packs a batch (eviction must write the
    # packed states back), one on "b"
    for h in (0, 1):
        pool.add(("a", h), masks[h], ds.C, jnp.zeros(n, jnp.float64), -y,
                 source="a")
    pool.add(("b", 0), masks[0], ds.C, jnp.zeros(n, jnp.float64), -y,
             source="b")
    pool.on_lane_chunk = lambda lid, state: pool.cache.get(
        "b" if lid[0] == "a" else "a")
    results = pool.run()
    assert pool.cache.peak_resident == 1
    # the reader forced evict -> re-materialize on nearly every chunk
    assert pool.cache.materializations > 3
    assert pool.cache.evictions > 2
    for (g, h) in results:
        K = specs[g].materialize().K
        seq = smo_solve(K, y, masks[h], ds.C, jnp.zeros(n), -y)
        np.testing.assert_array_equal(np.asarray(seq.alpha),
                                      np.asarray(results[(g, h)].alpha))
        np.testing.assert_array_equal(np.asarray(seq.f),
                                      np.asarray(results[(g, h)].f))
        assert int(seq.n_iter) == int(results[(g, h)].n_iter)


def test_pool_unbounded_width_budget_drains_sources():
    """The accelerator default (max_width=0, all live lanes dispatch) must
    NOT thrash a residency budget: per-chunk selection is restricted to
    budget-many managed sources, so each kernel materializes once — the
    count tracks sources, not chunks."""
    ds, X, y, n, masks = _setup("heart")
    specs = {g: KernelSpec(X=X, gamma=g * ds.gamma, n=n)
             for g in (0.5, 1.0, 2.0)}
    pool = LanePool(specs, y, chunk_iters=64, max_width=0, max_resident=1)
    for g in specs:
        for h in range(2):
            pool.add((g, h), masks[h], ds.C, jnp.zeros(n, jnp.float64), -y,
                     source=g)
    results = pool.run()
    assert pool.cache.materializations == len(specs)
    assert pool.cache.peak_resident == 1
    assert all(bool(r.converged) for r in results.values())


def test_pool_capped_selection_prefers_resident_sources():
    """Under a width cap, lanes whose kernel is already resident are
    selected before lanes that would force a materialization: a budgeted
    width-1 pool drains one source, then pays for the next — one
    materialization per source, no thrash."""
    ds, X, y, n, masks = _setup("heart")
    specs = {g: KernelSpec(X=X, gamma=g * ds.gamma, n=n)
             for g in (0.5, 1.0, 2.0)}
    pool = LanePool(specs, y, chunk_iters=64, max_width=1, max_resident=1)
    for g in specs:
        for h in range(2):
            pool.add((g, h), masks[h], ds.C, jnp.zeros(n, jnp.float64), -y,
                     source=g)
    pool.run()
    assert pool.cache.materializations == len(specs)
    assert pool.cache.peak_resident == 1


# ------------------------------------------------------- grid LRU parity

@pytest.mark.parametrize("name", SUITE)
def test_run_grid_lru_budgets_bit_parity(name):
    """run_grid under max_resident=1 / 2 / unbounded
    must produce bit-identical cells (iterations AND correct-counts) on
    every suite dataset — eviction/re-materialization schedules are
    unobservable in the results — while peak residency obeys the budget."""
    ds = make_dataset(name, n_override=100)
    kw = dict(Cs=[ds.C, 4 * ds.C], gammas=[0.5 * ds.gamma, 2 * ds.gamma],
              k=3, method="sir", chunk_iters=256)
    full = run_grid(ds, **kw)                       # unbounded: all resident
    assert full.resident["peak_resident"] == 2
    for budget in (1, 2):
        rep = run_grid(ds, max_resident=budget, **kw)
        assert rep.resident["peak_resident"] <= budget
        assert [(c.C, c.gamma, c.iterations, c.acc_correct, c.converged)
                for c in rep.cells] == \
            [(c.C, c.gamma, c.iterations, c.acc_correct, c.converged)
             for c in full.cells]
    assert full.kernel_time > 0


def test_run_grid_lru_kill_resume_cold_cache(tmp_path):
    """A killed budgeted grid resumes with a COLD cache (kernels are not
    checkpointed — specs re-materialize on demand) and lands on the
    identical per-cell report."""
    ds = make_dataset("heart", n_override=100)
    kw = dict(Cs=[ds.C, 4 * ds.C], gammas=[0.5 * ds.gamma, 2 * ds.gamma],
              k=3, method="sir", chunk_iters=64, max_resident=1)
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
    # the resumed study re-materialized (kernel_time covers it)
    assert resumed.resident["materializations"] >= 1
    assert resumed.kernel_time > 0


# ------------------------------------------- deferred fused/WSS validation

class _FusedFactory:
    """A factory whose product needs WSS-1 — only discoverable by
    materializing it."""

    def __init__(self, X, gamma):
        self.X, self.gamma = X, gamma

    @property
    def dtype(self):
        return self.X.dtype

    nbytes = 0
    fused = False          # the SPEC doesn't know; the product does

    def materialize(self):
        return FusedRBF(self.X, self.gamma)


def test_fused_source_validation_deferred_to_materialization():
    """A dense fused source still fails at pool construction; a FACTORY
    that produces a fused source passes construction (nothing is computed)
    and fails with the same error at first materialization."""
    ds, X, y, n, masks = _setup("heart")
    with pytest.raises(ValueError, match="requires WSS-1"):
        LanePool({"f": FusedRBF(X[:n], ds.gamma)}, y)
    pool = LanePool({"f": _FusedFactory(X[:n], ds.gamma)}, y)  # no raise
    pool.add(0, masks[0], ds.C, jnp.zeros(n, jnp.float64), -y)
    with pytest.raises(ValueError, match="requires WSS-1"):
        pool.run()
    # and wss="1" accepts the same factory end-to-end
    pool1 = LanePool({"f": _FusedFactory(X[:n], ds.gamma)}, y, wss="1")
    pool1.add(0, masks[0], ds.C, jnp.zeros(n, jnp.float64), -y)
    assert bool(pool1.run()[0].converged)


# ------------------------------------------------- plan validation at entry

def _one_lane_plan(K, y, masks, C):
    plan = Plan(sources={"s": DenseKernel(K)}, y=y)
    plan.lane(0, train_mask=masks[0], C=C,
              alpha0=jnp.zeros(y.shape[0]), f0=-y)
    return plan


def test_run_plan_validates_edges_by_name():
    """A typo'd dep/after edge, an unknown source key, or a cyclic graph
    fails AT ENTRY, naming the offending lane/edge — not hours later as
    LanePool.run's drain-time RuntimeError."""
    ds, X, y, n, masks = _setup("heart")
    K = np.asarray(kernel_matrix(X[:n], X[:n], gamma=ds.gamma))

    plan = _one_lane_plan(K, y, masks, ds.C)
    plan.lane(1, train_mask=masks[1], C=ds.C, dep="typo", transform="fold")
    with pytest.raises(ValueError,
                       match=r"lane 1: dep edge targets undeclared lane "
                             r"'typo'"):
        run_plan(plan)

    plan = _one_lane_plan(K, y, masks, ds.C)
    plan.lane(1, train_mask=masks[1], C=ds.C,
              alpha0=jnp.zeros(n), f0=-y, after=99)
    with pytest.raises(ValueError, match="after edge targets undeclared"):
        run_plan(plan)

    plan = _one_lane_plan(K, y, masks, ds.C)
    plan.lane(1, source="nope", train_mask=masks[1], C=ds.C,
              alpha0=jnp.zeros(n), f0=-y)
    with pytest.raises(ValueError, match="lane 1: unknown source key"):
        run_plan(plan)

    # a cycle is reported as the cycle, not as "every pending lane"
    plan = Plan(sources={"s": DenseKernel(K)}, y=y)
    plan.lane("a", train_mask=masks[0], C=ds.C, dep="b", transform="fold",
              params={})
    plan.lane("b", train_mask=masks[1], C=ds.C, dep="a", transform="fold",
              params={})
    with pytest.raises(ValueError, match="cycle"):
        run_plan(plan)

    plan = _one_lane_plan(K, y, masks, ds.C)
    plan.evaluate(42, np.arange(3))
    with pytest.raises(ValueError, match="EvalSpec targets undeclared"):
        run_plan(plan)


def test_run_plan_rejects_non_dense_pinned_source_at_entry():
    """A PINNED (already-materialized) source missing a required
    capability fails at entry — not after the dependency lane has solved
    for hours. Factories stay deferred (their product is unknowable
    without computing it). Evaluation is no longer such a capability for
    the RBF family: ``rows_at`` (shared since the shrinking
    reconstruction path, DESIGN.md §Shrinking) serves the eval row slab
    without a dense K, and must score identically to the dense path."""
    from repro.svm import DenseKernel, OnDemandRBF
    ds, X, y, n, masks = _setup("heart")
    plan = Plan(sources={"od": OnDemandRBF(X[:n], ds.gamma)}, y=y)
    plan.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
    plan.lane(1, train_mask=masks[1], C=ds.C, dep=0, transform="fold",
              params={})
    with pytest.raises(ValueError, match="transform 'fold' needs a dense"):
        run_plan(plan)

    def eval_plan(source):
        p = Plan(sources={"s": source}, y=y)
        p.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros(n), f0=-y)
        p.evaluate(0, np.arange(30))
        return run_plan(p)
    r_od = eval_plan(OnDemandRBF(X[:n], ds.gamma))
    K = kernel_matrix(X[:n], X[:n], gamma=ds.gamma)
    r_dense = eval_plan(DenseKernel(K))
    assert int(r_od.evals[0][0]) == int(r_dense.evals[0][0])


