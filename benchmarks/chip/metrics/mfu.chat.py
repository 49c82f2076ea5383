"""The decode step's share of the chip's bf16 peak, in percent: useful
FLOPs per step (every weight once per generated token, the readout, and
attention over the true context) over the measured device time per
step.  Averaged over the decode ticks in the traced stretch."""
from benchmarks.chip import model_math


def read(run):
    step_s = run.step_seconds()
    steps, emitted, attended = run.profile_decode()
    if step_s is None or run.peaks is None or not steps:
        return None
    flops = model_math.decode_flops(run.cfg, emitted, attended) / steps
    return 100.0 * flops / run.peaks["bf16_flop_s"] / step_s
