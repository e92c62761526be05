"""repro.analysis: plan analyzer, lint passes, scope derivation, baseline.

The headline test is the calibration contract: the plan analyzer's
predicted distinct-program count must match the MEASURED jit cache misses
of an actual grid run. Width-capped schedules realize every predicted
width deterministically, so caps 1 and 2 assert exact equality (one
gamma or three); the unbounded schedule is a can-produce upper bound —
lanes that converge in lockstep may never visit intermediate widths — so
it asserts measured <= predicted (DESIGN.md §Static analysis).
"""
import json
import pathlib
import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import findings, imports, jit_lint, kernel_lint
from repro.analysis.plan_check import (PlanAnalysis, _max_antichain,
                                       analyze_plan, check_plan)
from repro.core.grid import grid_plans, run_grid
from repro.core.study import Plan, run_plan
from repro.data.svm_suite import make_dataset
from repro.svm.engine import chunk_batched_jit, chunk_jit
from repro.svm.scheduler import possible_widths
from repro.svm.sources import KernelSpec

FIXTURES = pathlib.Path(__file__).parent / "fixtures" / "lint"


# ---------------------------------------------------------------- helpers

def _grid_kwargs(**over):
    kw = dict(k=3, method="sir", chunk_iters=512)
    kw.update(over)
    return kw


def _heart():
    return make_dataset("heart", n_override=120)


def _cs_gammas():
    return [1.0, 2.0, 4.0], [0.05, 0.1, 0.2]


def _small_plan(cache_bytes=0, evaluate=True):
    X = jnp.asarray(np.random.default_rng(0).normal(size=(16, 4)))
    y = jnp.asarray(np.where(np.arange(16) % 2, 1.0, -1.0))
    zeros = jnp.zeros(16)
    plan = Plan(sources={0: KernelSpec(X=X, gamma=0.5, kind="rbf")}, y=y,
                cache_bytes=cache_bytes)
    plan.lane("a", source=0, train_mask=y != 0, C=1.0, alpha0=zeros, f0=-y)
    plan.lane("b", source=0, train_mask=y != 0, C=2.0, alpha0=zeros, f0=-y,
              after="a")
    if evaluate:
        plan.evaluate("a", jnp.arange(4))
        plan.evaluate("b", jnp.arange(4))
    return plan


# ------------------------------------------------- predicted vs measured

def _predicted(gammas, max_width):
    (plan,) = grid_plans(_heart(), _cs_gammas()[0], gammas,
                         max_width=max_width, **_grid_kwargs())
    pa = analyze_plan(plan)
    assert pa.ok, pa.report.render()
    return len(set(map(tuple, pa.programs)))


def _measured(gammas, max_width):
    chunk_jit.clear_cache()
    chunk_batched_jit.clear_cache()
    run_grid(_heart(), _cs_gammas()[0], gammas, max_width=max_width,
             **_grid_kwargs())
    return chunk_jit._cache_size() + chunk_batched_jit._cache_size()


@pytest.mark.parametrize("gammas", [_cs_gammas()[1][:1], _cs_gammas()[1]],
                         ids=["one_gamma", "all_gammas"])
@pytest.mark.parametrize("max_width", [1, 2])
def test_predicted_programs_match_measured_compiles(gammas, max_width):
    """Width-capped schedules: analyzer prediction == jit cache misses,
    exactly. The jit cache is global, so the sources of a multi-gamma
    pool share compiles — same count as a one-gamma pool."""
    assert _predicted(gammas, max_width) == _measured(gammas, max_width) \
        == max_width


def test_unbounded_width_is_an_upper_bound():
    """max_width=0 (uncapped): every predicted width CAN occur, but a
    lockstep schedule may skip intermediate ones — measured never exceeds
    predicted."""
    gammas = _cs_gammas()[1]
    predicted = _predicted(gammas, 0)
    assert predicted == len(possible_widths(3, 4, 0)) == 3
    assert _measured(gammas, 0) <= predicted


def test_analyzer_enumerates_exact_grid_plans():
    """grid_plans IS run_grid's builder: per-source peaks reflect the
    fold-chain DAG (3 independent cells per gamma, folds chained)."""
    (plan,) = grid_plans(_heart(), *_cs_gammas(), **_grid_kwargs())
    pa = analyze_plan(plan)
    assert pa.ok
    assert set(pa.per_source) == {0, 1, 2}
    for src in pa.per_source.values():
        assert src["lanes"] == 9          # 3 cells x 3 folds
        assert src["peak_width"] == 3     # fold chains serialize each cell
        assert src["peak_exact"]


# ------------------------------------------------------- plan feasibility

def test_rejects_plan_exceeding_cache_bytes():
    """A factory source larger than the declared budget is statically
    infeasible — check_plan and run_plan(strict) both refuse before any
    kernel materializes."""
    plan = _small_plan(cache_bytes=1000)   # dense 16x16 f64 K = 2048 B
    pa = analyze_plan(plan)
    assert not pa.ok
    assert any(f.rule == "cache-infeasible" for f in pa.report.errors)
    with pytest.raises(ValueError, match="cache-infeasible"):
        check_plan(plan)
    with pytest.raises(ValueError, match="cache-infeasible"):
        run_plan(_small_plan(cache_bytes=1000), analysis="strict")


def test_findings_context_tags_without_changing_identity(tmp_path):
    """``analyze_plan(..., context=...)`` stamps every finding with the
    caller's attribution (the daemon passes ``tenant/plan_id``): rendered
    output names it, but the baseline identity key is untouched — a
    context-tagged finding is still accepted by a context-free baseline,
    and the line-free key semantics survive."""
    plan = _small_plan(cache_bytes=1000)
    plain = analyze_plan(plan)
    tagged = analyze_plan(plan, context="alice/p1")
    assert all(f.context == "alice/p1" for f in tagged.report)
    assert all(f.context == "" for f in plain.report)
    assert all(" [alice/p1]: " in f.render() for f in tagged.report)
    # identity excludes context: the same findings, to a baseline
    assert {f.key for f in tagged.report} == {f.key for f in plain.report}
    base = findings.write_baseline(plain.report, tmp_path / "base.json")
    assert tagged.report.new_against(base) == []
    with pytest.raises(ValueError) as ei:
        check_plan(plan, context="alice/p1")
    assert "alice/p1" in str(ei.value)


def test_admits_plan_within_cache_bytes():
    pa = analyze_plan(_small_plan(cache_bytes=1 << 20))
    assert pa.ok
    assert pa.peak_managed_bytes == 16 * 16 * 8


def test_checkpoint_base_step_audit():
    plan = _small_plan()
    bad = types.SimpleNamespace(base_step=5)
    pa = analyze_plan(plan, checkpoint=bad)
    assert any(f.rule == "checkpoint-key-collision" and "mid-fold"
               in f.message for f in pa.report.errors)
    batch = types.SimpleNamespace(base_step=10 ** 12)
    pa = analyze_plan(plan, checkpoint=batch)
    assert any(f.rule == "checkpoint-key-collision" and "batch"
               in f.message for f in pa.report.errors)
    ok = types.SimpleNamespace(base_step=2 * 10 ** 12)
    assert analyze_plan(plan, checkpoint=ok).ok


def test_dead_lane_is_advisory():
    plan = _small_plan(evaluate=False)
    pa = analyze_plan(plan)
    assert pa.ok                          # warns are not errors
    unobserved = [f for f in pa.report if f.rule == "lane-unobserved"]
    assert [f.symbol for f in unobserved] == ["'b'"]   # 'a' feeds 'b'


def test_invalid_plan_becomes_finding_not_crash():
    plan = _small_plan()
    plan.lane("a", source=0, train_mask=plan.y != 0, C=1.0,
              alpha0=jnp.zeros(16), f0=-plan.y)   # duplicate id
    pa = analyze_plan(plan)
    assert not pa.ok
    assert pa.report.errors[0].rule == "invalid-plan"
    assert "duplicate" in pa.report.errors[0].message


def test_run_plan_attaches_advisory_analysis():
    sr = run_plan(_small_plan())
    assert isinstance(sr.analysis, PlanAnalysis)
    assert sr.analysis.ok and sr.analysis.program_count >= 1
    assert run_plan(_small_plan(), analysis="off").analysis is None
    with pytest.raises(ValueError, match="analysis"):
        run_plan(_small_plan(), analysis="loud")


# ----------------------------------------------------------- antichain

def test_max_antichain_chain_and_independent():
    chain = {i: [i - 1] for i in range(1, 5)}
    chain[0] = []
    assert _max_antichain(list(range(5)), chain) == 1
    assert _max_antichain(list(range(5)), {i: [] for i in range(5)}) == 5


def test_max_antichain_grid_row_dag():
    """3 cells x 3 folds, folds chained within a cell: peak is the cell
    count, and chaining fold 0 across cells (seed_across_C) does not
    change it (the antichain picks one lane per cell at skewed depths)."""
    prereqs = {(c, h): ([(c, h - 1)] if h else []) for c in range(3)
               for h in range(3)}
    nodes = list(prereqs)
    assert _max_antichain(nodes, prereqs) == 3
    for c in range(1, 3):
        prereqs[(c, 0)] = [(c - 1, 0)]
    assert _max_antichain(nodes, prereqs) == 3


def test_possible_widths_buckets_and_caps():
    assert possible_widths(3, 4, 0) == (1, 2, 4)
    assert possible_widths(3, 4, 1) == (1,)
    assert possible_widths(3, 4, 2) == (1, 2)
    assert possible_widths(9, 4, 0) == (1, 2, 4, 8, 12)
    assert possible_widths(1, 4, 0) == (1,)


# ------------------------------------------------------------ lint passes

def _rules(report):
    return {f.rule for f in report}


def test_jit_lint_fixture_nonzero():
    rpt = jit_lint.lint_paths([FIXTURES / "bad_nonzero.py"])
    assert _rules(rpt) == {"unsized-nonzero"}
    assert [f.symbol for f in rpt] == ["support_vectors"]   # sized one OK


def test_jit_lint_fixture_branch_and_cast():
    rpt = jit_lint.lint_paths([FIXTURES / "bad_branch.py"])
    assert _rules(rpt) == {"traced-python-branch", "traced-host-cast"}
    assert "static_branch_ok" not in {f.symbol for f in rpt}


def test_jit_lint_fixture_timer():
    rpt = jit_lint.lint_paths([FIXTURES / "bad_timer.py"])
    assert _rules(rpt) == {"timer-no-sync"}
    assert [f.symbol for f in rpt] == ["timed_norm"]        # synced one OK


def test_kernel_lint_fixture_all_rules():
    rpt = kernel_lint.lint_paths([FIXTURES / "bad_kernel.py"])
    assert _rules(rpt) == {"auto-interpret-contract", "block-divisibility",
                           "vmem-footprint", "acc-dtype-promotion"}


def test_lint_scope_is_clean_against_baseline():
    """The derived scope must carry no findings beyond the committed
    baseline — the same gate CI runs."""
    repo = pathlib.Path(__file__).parents[1]
    scope = imports.default_scope()
    rpt = jit_lint.lint_paths(scope, repo_root=repo)
    rpt.extend(kernel_lint.lint_paths(
        [p for p in scope if "kernels" in p.parts], repo_root=repo))
    baseline = findings.load_baseline(repo / "results"
                                      / "lint_baseline.json")
    assert baseline is not None
    new = rpt.new_against(baseline)
    assert not new, "\n".join(f.render() for f in new)


# ------------------------------------------------------- scope derivation

def test_scaffolding_inventory_excludes_svm_tree():
    scaffolding = imports.scaffolding_inventory()
    assert not any(m.startswith(("repro.svm", "repro.core",
                                 "repro.kernels", "repro.analysis",
                                 "repro.checkpoint"))
                   for m in scaffolding)
    assert "repro.models.transformer" in scaffolding
    assert "repro.training.train_step" in scaffolding
    assert "repro.configs.base" in scaffolding


def test_default_scope_tracks_imports():
    scope = {p.name for p in imports.default_scope()}
    assert {"engine.py", "scheduler.py", "sources.py", "cv.py",
            "grid.py", "study.py", "svm_suite.py"} <= scope
    assert "transformer.py" not in scope
    # sharding is adopted: engine.py imports repro.sharding
    assert "sharding" in {p.parent.name for p in imports.default_scope()}


# ------------------------------------------------------ findings/baseline

def test_baseline_roundtrip_and_gate(tmp_path):
    rpt = findings.Report()
    rpt.add("r1", "a.py", "f", "msg one")
    rpt.add("r2", "b.py", "g", "msg two", severity="warn", line=7)
    path = tmp_path / "base.json"
    findings.write_baseline(rpt, path)
    base = findings.load_baseline(path)
    assert rpt.new_against(base) == []
    rpt.add("r3", "c.py", "h", "fresh")
    new = rpt.new_against(base)
    assert [f.rule for f in new] == ["r3"]
    # identity survives line drift
    moved = findings.Report()
    moved.add("r1", "a.py", "f", "msg one", line=99)
    assert moved.new_against(base) == []


def test_baseline_refresh_keeps_justifications(tmp_path):
    rpt = findings.Report()
    rpt.add("r1", "a.py", "f", "msg")
    path = tmp_path / "base.json"
    data = findings.write_baseline(rpt, path)
    data["findings"][0]["justification"] = "accepted: by design"
    path.write_text(json.dumps(data))
    refreshed = findings.write_baseline(rpt, path,
                                        previous=findings.load_baseline(path))
    assert refreshed["findings"][0]["justification"] == "accepted: by design"
