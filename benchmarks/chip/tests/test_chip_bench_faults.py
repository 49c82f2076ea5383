"""The correctness check fails a broken timed path.

Each case drives the rest of a run (rehearsal size, on the CPU, past the
harness's look for a chip) with the decode program broken underneath:
emitted tokens altered where the program produces them, and a step that
leaves its KV state as it was (``faults.py``).  The unbroken run passes
the same check.  A cell on one chip has no exchange between chips to
leave out; the four-chip cell's faults are in ``test_chip_bench_mesh.py``.
"""
import time

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests.faults import alter_tokens, keep_state

CELL = "qwen2_5_3b.reason_decode"


def run(tamper):
    return harness.execute(CELL, 2**31 + 99, 2.0, False,
                           t_process=time.perf_counter(), rehearse=True,
                           tamper=tamper)


def test_unbroken_run_is_correct():
    res = run(None)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("tamper", [alter_tokens, keep_state])
def test_broken_run_is_not_correct(tamper):
    res = run(tamper)
    assert not res["correct"], res["checks"]
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
