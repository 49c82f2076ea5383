"""Useful FLOPs over the window's seconds at the chip's bf16 peak, in
percent: prompt tokens computed (not those served from the prefix
cache) with one readout each, and generated tokens with their readout
and attention over their true context."""
from benchmarks.chip import model_math


def read(run):
    if run.peaks is None:
        return None
    flops = sum(model_math.prefill_flops(run.cfg, r.prompt_len, r.reuse)
                for r in run.admitted_in_window())
    for t, emitted, attended, _ in run.harvests:
        if run.in_window(t):
            flops += model_math.decode_flops(run.cfg, emitted, attended)
    return 100.0 * flops / run.peaks["bf16_flop_s"] / run.window_s()
