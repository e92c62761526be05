"""The program's span recorder and program-load counter (``repro.obs``):
span ids and plan ids, the bounded ring, compile events charged to the
open span, ``run_plan``'s span tree, the pool's timings taken from its
spans, and the spans in a profiler trace on the device's clock."""
import pathlib
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.study import Plan, run_plan
from repro.data.svm_suite import kfold_chunks, make_dataset
from repro.svm import DenseKernel, kernel_matrix
from repro.svm.precision import kernel_input


def test_parent_and_plan_ids_nest():
    rec = obs.Recorder()
    with rec.span(obs.PLAN) as p1:
        with rec.span("repro.pool.run") as run:
            with rec.span("repro.pool.wait") as wait:
                pass
    with rec.span(obs.PLAN) as p2:
        with rec.span("repro.plan.evals") as ev:
            pass
    with rec.span("repro.cache.materialize") as free:
        pass
    got = {s.id: s for s in rec.records()}
    assert got[run.id].parent == p1.id and got[wait.id].parent == run.id
    assert got[p1.id].parent is None and got[free.id].parent is None
    assert got[ev.id].parent == p2.id
    plan1, plan2 = p1.attrs["plan"], p2.attrs["plan"]
    assert plan1 != plan2
    assert run.attrs["plan"] == wait.attrs["plan"] == plan1
    assert ev.attrs["plan"] == plan2
    assert "plan" not in free.attrs
    # children exit first, so the ring holds them before their parents
    assert [s.name for s in rec.records()][:3] == [
        "repro.pool.wait", "repro.pool.run", obs.PLAN]
    for s in rec.records():
        assert s.t0_ns <= s.t1_ns
    assert got[p1.id].t0_ns <= got[run.id].t0_ns <= got[wait.id].t0_ns
    assert got[wait.id].t1_ns <= got[run.id].t1_ns <= got[p1.id].t1_ns


def test_stacks_are_per_thread():
    rec = obs.Recorder()
    seen = {}

    def other():
        with rec.span("repro.pool.wait") as sp:
            seen["parent"], seen["attrs"] = sp.parent, dict(sp.attrs)

    with rec.span(obs.PLAN):
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=30)
    assert not t.is_alive()
    assert seen == {"parent": None, "attrs": {}}


def test_ring_is_bounded_and_counts_dropped():
    rec = obs.Recorder(maxlen=3)
    for i in range(5):
        with rec.span("repro.pool.wait", i=i):
            pass
    assert len(rec.spans) == 3 and rec.dropped["spans"] == 2
    assert [s.attrs["i"] for s in rec.records()] == [2, 3, 4]
    for i in range(4):
        rec.on_event("/jax/core/compile/backend_compile_duration", 0.5)
    rec.on_event("/jax/some/other_event", 1.0)
    assert len(rec.events) == 3 and rec.dropped["events"] == 1
    rec.reset()
    assert not rec.spans and not rec.events
    assert rec.dropped == {"spans": 0, "events": 0}


def test_compile_inside_a_span_is_counted_against_it():
    obs.reset()

    def fresh_program(x):
        return jnp.cos(x) * 3.0 + 1.0

    with obs.span("repro.test.compile"):
        jax.jit(fresh_program)(jnp.arange(7.0)).block_until_ready()
    mine = [e for e in obs.events() if e.span == "repro.test.compile"]
    assert {"trace", "lower", "compile"} <= {e.name for e in mine}
    assert any(e.fun == "fresh_program" for e in mine if e.name == "trace")
    assert all(0.0 <= e.own_s <= e.secs for e in mine)
    totals = obs.counters()
    for name in ("trace", "lower", "compile"):
        n, s = totals[name]
        assert n >= 1 and s > 0.0
    assert totals["dropped"] == {"spans": 0, "events": 0}


def test_nested_trace_is_not_counted_twice():
    obs.reset()

    def inner_program(x):
        return x * 2.0

    inner = jax.jit(inner_program)

    def outer_program(x):
        return inner(x) + 1.0

    jax.jit(outer_program)(jnp.arange(5.0)).block_until_ready()
    traces = {e.fun: e for e in obs.events() if e.name == "trace"}
    i, o = traces["inner_program"], traces["outer_program"]
    assert i.t_ns <= o.t_ns
    assert o.own_s <= o.secs - i.secs + 1e-9


@pytest.fixture(scope="module")
def fold_plan_run():
    """A two-lane ``run_plan``: fold 0 cold, fold 1 seeded from it by SIR,
    in chunks of 64 iterations, with its span record."""
    ds = make_dataset("heart", n_override=120)
    X = kernel_input(ds.X)
    y = jnp.asarray(ds.y, jnp.float64)
    K = kernel_matrix(X, X, kind="rbf", gamma=ds.gamma)
    chunks = kfold_chunks(int(y.shape[0]), 5, seed=0)
    masks = []
    for h in range(2):
        m = np.ones(y.shape[0], bool)
        m[chunks[h]] = False
        masks.append(jnp.asarray(m))
    plan = Plan(sources={"cv": DenseKernel(K)}, y=y, chunk_iters=64)
    plan.lane(0, train_mask=masks[0], C=ds.C, alpha0=jnp.zeros_like(y),
              f0=-y)
    plan.lane(1, dep=0, transform="fold", train_mask=masks[1], C=ds.C,
              params=dict(method="sir", S_idx=jnp.asarray(chunks[1]),
                          R_idx=jnp.asarray(chunks[0]),
                          T_idx=jnp.asarray(chunks[0])))
    plan.evaluate(1, jnp.asarray(chunks[1]))
    obs.reset()
    res = run_plan(plan)
    return res, obs.records()


def test_run_plan_emits_the_span_tree(fold_plan_run):
    res, spans = fold_plan_run
    plans = [s for s in spans if s.name == obs.PLAN]
    assert len(plans) == 1
    top = plans[0]
    assert top.attrs["lanes"] == ((0, res.stats[0].n_iter),
                                  (1, res.stats[1].n_iter))
    mine = [s for s in spans if s.attrs.get("plan") == top.attrs["plan"]]
    assert len(mine) == len(spans)
    by_id = {s.id: s for s in spans}

    def under(name):
        return {by_id[s.parent].name for s in spans if s.name == name}

    assert {s.name for s in spans if s.parent == top.id} == {
        "repro.plan.prepare", "repro.plan.analyze", "repro.pool.build",
        "repro.pool.run", "repro.plan.evals", "repro.plan.release"}
    assert under("repro.pool.seed") == {"repro.pool.run"}
    assert under("repro.pool.dispatch") == {"repro.pool.run"}
    assert under("repro.pool.wait") == {"repro.pool.dispatch",
                                        "repro.pool.run"}
    assert under("repro.pool.retire") == {"repro.pool.dispatch"}
    seeds = [s for s in spans if s.name == "repro.pool.seed"]
    assert [(s.attrs["lane"], s.attrs["transform"]) for s in seeds] == [
        (1, "fold")]
    dispatches = [s for s in spans if s.name == "repro.pool.dispatch"]
    assert [s.attrs["chunk"] for s in dispatches] == list(
        range(len(dispatches)))
    assert sum(s.name == "repro.pool.retire" for s in spans) == 2
    for s in spans:
        if s.parent is not None:
            p = by_id[s.parent]
            assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns


def test_lane_times_are_the_spans_clock_readings(fold_plan_run):
    res, spans = fold_plan_run
    for lane in (0, 1):
        seed_s = solve_s = 0.0
        for s in spans:
            if s.name == "repro.pool.seed" and s.attrs["lane"] == lane:
                seed_s += (s.t1_ns - s.t0_ns) / 1e9 - s.attrs["kernel_s"]
            if s.name == "repro.pool.dispatch" and lane in s.attrs["lanes"]:
                dt = (s.t1_ns - s.t0_ns) / 1e9 - s.attrs["kernel_s"]
                solve_s += dt / len(s.attrs["lanes"])
        assert res.stats[lane].seed_s == seed_s
        assert res.stats[lane].solve_s == solve_s
        assert solve_s > 0.0
    assert res.stats[1].seed_s > 0.0 and res.stats[0].seed_s == 0.0
    assert res.seed_time == res.stats[1].seed_s


def test_spans_land_in_a_profiler_trace_on_the_host_plane(tmp_path):
    from jax.profiler import ProfileData, TraceAnnotation
    obs.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TraceAnnotation("test.outer"):
            with obs.span(obs.PLAN):
                with obs.span("repro.pool.wait"):
                    jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_path).glob(
        "plugins/profile/*/*.xplane.pb"))[-1]
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name in ("test.outer", obs.PLAN, "repro.pool.wait"):
                    found[e.name] = (plane.name, e.start_ns,
                                     e.start_ns + e.duration_ns)
    assert {v[0] for v in found.values()} == {"/host:CPU"}
    outer, plan, wait = (found[k] for k in ("test.outer", obs.PLAN,
                                            "repro.pool.wait"))
    assert outer[1] <= plan[1] <= wait[1] <= wait[2] <= plan[2] <= outer[2]
