"""Seconds per fold of the seed's reads of a row-streaming source: the
``repro.seed.block`` spans (SIR's K[R, T] block computed from X) and the
``repro.seed.matvec`` spans (f0 through the streaming ``matvec``), each
synced, inside the ``repro.pool.seed`` span of the fold's seed transform,
of each window fold, summed and divided by the number of folds. Read from
the program's span record (``obsread``); None where the program keeps no
record or records no such span (a dense K, or a program that cannot seed
without one)."""
import obsread

STREAM = ("repro.seed.block", "repro.seed.matvec")


def read(run):
    rec = obsread.record()
    if rec is None:
        return None
    plans = obsread.window_plans(run, rec[0])
    if plans is None:
        return None
    spans = [s for _, group in plans for s in group if s.name in STREAM]
    if not spans:
        return None
    return sum(obsread.seconds(s) for s in spans) / len(plans)
