"""Seconds per fold outside seed init and SMO solve: the step's wall time
on the benchmark's clock minus the program's ``seed_s`` and ``solve_s``.
It holds plan validation and analysis, pool construction, dispatch
bookkeeping and the held-out evaluation."""


def read(run):
    rest = sum(f["wall_s"] - f["seed_s"] - f["solve_s"] for f in run.folds)
    return rest / len(run.folds)
