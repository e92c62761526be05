"""Microseconds of SMO solve per iteration: the program's
``LaneStat.solve_s`` (its host clock around each synced chunk dispatch)
summed over the window's folds, over their summed ``n_iter``."""


def read(run):
    iters = sum(f["n_iter"] for f in run.folds)
    if not iters:
        return None
    return 1e6 * sum(f["solve_s"] for f in run.folds) / iters
