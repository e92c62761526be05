"""SMO iterations per fold: the program's ``LaneStat.n_iter``, averaged
over the window's folds. A count: it repeats exactly for one seed."""


def read(run):
    return sum(f["n_iter"] for f in run.folds) / len(run.folds)
