"""Share of the traced stretch in which no operation ran on the chip, in
percent (1 - busy union / stretch, from the profiler trace)."""


def read(run):
    if run.reduced is None:
        return None
    return 100.0 * run.reduced.idle_share
