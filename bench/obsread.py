"""The program's own record of its spans and program loads (``repro.obs``),
matched to the window's folds, for the readers of ``plan_s``,
``pool_host_s`` and ``setup_programs_s``.

The record holds every ``run_plan`` call of the run: set-up's folds, the
window's, and the traced ones after it. The window's are found by what
they solved, never by position: the run of consecutive ``repro.plan``
spans whose solved lanes (``attrs["lanes"]``, ``(lane, n_iter)`` pairs)
hold each window fold's ``n_iter`` in order, and whose
``repro.pool.dispatch`` spans give that lane the fold's ``solve_s``, the
same clock readings the program summed. A checkout whose program keeps
no record, a match that fails or one that is not unique gives None.
"""
from __future__ import annotations

import math

PLAN = "repro.plan"
RUN = "repro.pool.run"
DISPATCH = "repro.pool.dispatch"
#: spans in which the pool's host waits on the device, or builds a kernel
BLOCKING = ("repro.pool.wait", "repro.pool.seed", "repro.cache.materialize")


def record():
    """``(spans, events, dropped)`` from ``repro.obs``, or None where the
    program has no such module."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.records(), obs.events(), obs.counters()["dropped"]


def by_plan(spans) -> dict:
    """``{plan id: [span]}`` of every span that carries a plan id."""
    out: dict = {}
    for s in spans:
        if "plan" in s.attrs:
            out.setdefault(s.attrs["plan"], []).append(s)
    return out


def lane_solve_s(spans, lane) -> float:
    """``lane``'s ``solve_s`` from one plan's dispatch spans, summed as the
    pool sums it: each chunk's seconds less its kernel time, shared among
    the chunk's lanes."""
    total = 0.0
    for s in spans:
        if s.name == DISPATCH and lane in s.attrs["lanes"]:
            dt = (s.t1_ns - s.t0_ns) / 1e9 - s.attrs["kernel_s"]
            total += dt / len(s.attrs["lanes"])
    return total


def _solved(fold: dict, plan, spans) -> bool:
    lanes = [lane for lane, n in plan.attrs["lanes"] if n == fold["n_iter"]]
    if fold.get("solve_s") is None:
        return bool(lanes)
    return any(math.isclose(lane_solve_s(spans, lane), fold["solve_s"],
                            rel_tol=1e-9, abs_tol=1e-12) for lane in lanes)


def window_plans(run, spans):
    """``[(plan span, spans of that plan)]`` of the window's folds, in
    order, or None (see the module docstring)."""
    plans = [s for s in spans if s.name == PLAN and "lanes" in s.attrs]
    groups = by_plan(spans)
    n = len(run.folds)
    found = None
    for i in range(len(plans) - n + 1):
        cand = plans[i:i + n]
        if all(_solved(f, p, groups[p.attrs["plan"]])
               for f, p in zip(run.folds, cand)):
            if found is not None:
                return None
            found = cand
    if found is None:
        return None
    return [(p, groups[p.attrs["plan"]]) for p in found]


def seconds(s) -> float:
    return (s.t1_ns - s.t0_ns) / 1e9


def outermost(spans, top, names) -> list:
    """The spans named in ``names`` below ``top`` (of one plan's
    ``spans``) that lie inside no other such span below ``top``."""
    parent = {s.id: s.parent for s in spans}
    name = {s.id: s.name for s in spans}
    out = []
    for s in spans:
        if s.name not in names:
            continue
        up = s.parent
        while up is not None and up != top.id and name.get(up) not in names:
            up = parent.get(up)
        if up == top.id:
            out.append(s)
    return out
