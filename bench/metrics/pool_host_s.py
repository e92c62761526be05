"""Seconds per fold of the lane pool's own host work: each window fold's
``repro.pool.run`` span less the outermost spans below it in which the
host waits on the device (``repro.pool.wait``), runs a seed transform
(``repro.pool.seed``) or builds a kernel (``repro.cache.materialize``).
What is left is selection, packing, enqueueing chunks and retirement,
averaged over the window's folds. Read from the program's span record
(``obsread``); None where the program keeps none."""
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
        for top in spans:
            if top.name == obsread.RUN and top.parent == plan.id:
                total += obsread.seconds(top) - sum(
                    obsread.seconds(s) for s in
                    obsread.outermost(spans, top, obsread.BLOCKING))
    return total / len(plans)
