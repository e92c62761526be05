"""Seconds of set-up spent on programs: the own seconds (``own_s``, less
the events nested in each) of the program-load counter's trace, lower,
compile and cache-load events that ended before the window's first
``repro.plan`` span started. None where the program keeps no counter,
where the window's folds cannot be matched, or where the counter's ring
dropped events."""
import obsread


def read(run):
    rec = obsread.record()
    if rec is None:
        return None
    spans, events, dropped = rec
    plans = obsread.window_plans(run, spans)
    if plans is None or dropped["events"]:
        return None
    start = plans[0][0].t0_ns
    return sum(e.own_s for e in events if e.t_ns <= start)
