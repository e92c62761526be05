"""Seconds per fold of ``run_plan``'s own work: each window fold's
``repro.plan`` span less its ``repro.pool.run`` child, that is plan
preparation and validation, the advisory plan analysis, pool build and
enrolment, the held-out evaluations of the plan and the release of its
sources, averaged over the window's folds. Read from the program's span
record (``obsread``); None where the program keeps none."""
import obsread


def read(run):
    rec = obsread.record()
    if rec is None:
        return None
    plans = obsread.window_plans(run, rec[0])
    if plans is None:
        return None
    total = 0.0
    for plan, spans in plans:
        total += obsread.seconds(plan) - sum(
            obsread.seconds(s) for s in spans
            if s.name == obsread.RUN and s.parent == plan.id)
    return total / len(plans)
