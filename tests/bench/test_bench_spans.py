"""The readers of ``plan_s``, ``pool_host_s`` and ``setup_programs_s`` on a
synthetic run and span record: the window's folds found by what they
solved, the numbers each reader takes from them, and None wherever the
match fails or the program keeps no record."""
import pathlib
import sys
import types

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

import obsread  # noqa: E402
import run  # noqa: E402

from repro.obs import Event, Span  # noqa: E402

READERS = {name: run.load_module(ROOT / "bench" / "metrics" / f"{name}.py")
           for name in ("plan_s", "pool_host_s", "setup_programs_s")}
MS = 1_000_000


class Record:
    """Spans of consecutive synthetic ``run_plan`` calls, one lane each."""

    def __init__(self):
        self.spans, self.ids = [], 0

    def add(self, parent, name, t0, t1, **attrs):
        self.ids += 1
        self.spans.append(Span(self.ids, parent, name, t0 * MS, t1 * MS,
                               attrs))
        return self.ids

    def plan(self, plan, base, lane, n_iter):
        """One fold's plan over [base, base + 100] ms: 30 ms outside
        ``repro.pool.run`` and, inside its 70 ms, 10 ms of seed transform
        (a 4 ms kernel build in it) and 49 ms of waits: 11 ms of host work.
        The dispatch takes 53 ms, 2 ms of it a kernel build."""
        b = base
        top = self.add(None, "repro.plan", b, b + 100, plan=plan,
                       lanes=((lane, n_iter),))
        self.add(top, "repro.plan.prepare", b, b + 5, plan=plan)
        self.add(top, "repro.plan.analyze", b + 5, b + 15, plan=plan)
        self.add(top, "repro.pool.build", b + 15, b + 20, plan=plan)
        run_ = self.add(top, "repro.pool.run", b + 20, b + 90, plan=plan)
        seed = self.add(run_, "repro.pool.seed", b + 21, b + 31, plan=plan,
                        lane=lane, transform="fold", kernel_s=0.004)
        self.add(seed, "repro.cache.materialize", b + 22, b + 26, plan=plan)
        disp = self.add(run_, "repro.pool.dispatch", b + 32, b + 85,
                        plan=plan, chunk=0, width=1, lanes=(lane,),
                        kernel_s=0.002)
        self.add(disp, "repro.pool.wait", b + 33, b + 80, plan=plan)
        self.add(disp, "repro.pool.retire", b + 81, b + 84, plan=plan,
                 lane=lane)
        self.add(run_, "repro.pool.wait", b + 86, b + 88, plan=plan)
        self.add(top, "repro.plan.evals", b + 90, b + 97, plan=plan)
        self.add(top, "repro.plan.release", b + 97, b + 99, plan=plan)


SOLVE_S = 0.053 - 0.002


def _fold(n_iter, solve_s=SOLVE_S):
    return dict(fold=0, seed_from=-1, n_iter=n_iter, converged=True,
                seed_s=0.006, solve_s=solve_s, wall_s=0.2)


def _setup(monkeypatch, folds, *, iters=(100, 200, 300, 400), events=(),
           dropped=0):
    """Plans 1..4 a second apart (set-up, the two window folds, a traced
    fold), recorded as the program would, and a run of ``folds``."""
    rec = Record()
    for i, n in enumerate(iters):
        rec.plan(i + 1, 1000 * (i + 1), lane=i, n_iter=n)
    monkeypatch.setattr(obsread, "record", lambda: (
        rec.spans, list(events), {"spans": 0, "events": dropped}))
    return types.SimpleNamespace(folds=folds, trace=None, cfg={},
                                 traffic={"method": "cold"})


def _events():
    def ev(name, own, t_ms):
        return Event(name, own, own, t_ms * MS, "outside", None)
    # before the window's first plan (2000 ms), then inside the window
    return [ev("trace", 0.25, 10), ev("compile", 1.5, 500),
            ev("cache_load", 0.75, 1500), ev("compile", 4.0, 2050)]


def test_readers_on_the_window_folds(monkeypatch):
    r = _setup(monkeypatch, [_fold(200), _fold(300)], events=_events())
    assert READERS["plan_s"].read(r) == pytest.approx(0.030)
    assert READERS["pool_host_s"].read(r) == pytest.approx(0.011)
    assert READERS["setup_programs_s"].read(r) == pytest.approx(2.5)


def test_window_is_found_by_what_it_solved(monkeypatch):
    r = _setup(monkeypatch, [_fold(300), _fold(400)], events=_events())
    plans = obsread.window_plans(r, obsread.record()[0])
    assert [p.attrs["plan"] for p, _ in plans] == [3, 4]
    # the window now starts at 3000 ms, after the compile at 2050 ms
    assert READERS["setup_programs_s"].read(r) == pytest.approx(6.5)


@pytest.mark.parametrize("case", [
    "n_iter", "solve_s", "order", "too_many", "ambiguous", "dropped",
    "no_program"])
def test_a_failed_match_gives_none(monkeypatch, case):
    folds = [_fold(200), _fold(300)]
    kw = dict(events=_events())
    if case == "n_iter":
        folds[1] = _fold(301)
    elif case == "solve_s":
        folds[0] = _fold(200, solve_s=SOLVE_S * 1.001)
    elif case == "order":
        folds = folds[::-1]
    elif case == "too_many":
        folds = [_fold(n) for n in (100, 200, 300, 400, 500)]
    elif case == "ambiguous":
        kw["iters"] = (200, 300, 200, 300)
    elif case == "dropped":
        kw["dropped"] = 1
    r = _setup(monkeypatch, folds, **kw)
    if case == "no_program":
        monkeypatch.setattr(obsread, "record", lambda: None)
    got = {name: m.read(r) for name, m in READERS.items()}
    if case == "dropped":
        # a dropped event loses set-up's count, not the spans' match
        assert got["setup_programs_s"] is None
        assert got["plan_s"] == pytest.approx(0.030)
    else:
        assert got == dict.fromkeys(READERS)


def test_without_floats_the_match_uses_n_iter(monkeypatch):
    r = _setup(monkeypatch, [_fold(200, None), _fold(300, None)])
    assert READERS["plan_s"].read(r) == pytest.approx(0.030)


def test_outermost_skips_what_nests_in_a_blocking_span():
    rec = Record()
    rec.plan(1, 0, lane=0, n_iter=10)
    run_ = next(s for s in rec.spans if s.name == "repro.pool.run")
    got = obsread.outermost(rec.spans, run_, obsread.BLOCKING)
    assert sorted(s.name for s in got) == [
        "repro.pool.seed", "repro.pool.wait", "repro.pool.wait"]
    assert obsread.lane_solve_s(rec.spans, 0) == pytest.approx(SOLVE_S)


def test_the_reader_reads_the_live_program_record():
    """Without a stand-in, ``record`` reads ``repro.obs`` itself."""
    spans, events, dropped = obsread.record()
    assert isinstance(spans, list) and isinstance(events, list)
    assert set(dropped) == {"spans", "events"}
