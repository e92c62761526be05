"""Seconds per fold of MIR's assembly: the ``repro.seed.assemble`` spans
(the normal equations streamed from K in blocks, synced, inside the
``repro.pool.seed`` span of the fold's seed transform) of each window
fold, summed and divided by the number of folds. Read from the program's
span record (``obsread``); None where the program keeps no record or
records no such span."""
import obsread

ASSEMBLE = "repro.seed.assemble"


def read(run):
    rec = obsread.record()
    if rec is None:
        return None
    plans = obsread.window_plans(run, rec[0])
    if plans is None:
        return None
    spans = [s for _, group in plans for s in group if s.name == ASSEMBLE]
    if not spans:
        return None
    return sum(obsread.seconds(s) for s in spans) / len(plans)
