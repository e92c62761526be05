"""Share of the traced window in which no operation ran on the chip, in
percent: 1 - busy / window from the profiler's device trace."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace["idle_share"]
