"""Decode step against its memory roofline, in percent: the least bytes a
step needs (the weights once, plus the live K/V of the active slots at
their true context lengths) over HBM bandwidth, divided by the measured
device time per step.  Averaged over the decode ticks whose tokens
reached the host in the traced stretch."""
from benchmarks.chip import model_math


def read(run):
    step_s = run.step_seconds()
    steps, _, attended = run.profile_decode()
    if step_s is None or run.peaks is None or not steps:
        return None
    least = model_math.decode_least_bytes(run.cfg, steps, attended) / steps
    return 100.0 * least / run.peaks["hbm_bytes_s"] / step_s
