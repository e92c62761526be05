"""Seconds of seed init per fold: the program's ``LaneStat.seed_s`` (its
host clock around the admission transform, synced, kernel time left
out), summed over the window's folds and divided by their number. Cold
folds run no transform, so a cold cell has nothing to read."""


def read(run):
    if run.traffic["method"] == "cold":
        return None
    return sum(f["seed_s"] for f in run.folds) / len(run.folds)
