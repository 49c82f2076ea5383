"""The control fails the check: the plain reference in the program's
place, in the next precision below the configurations' bfloat16.

At the rehearsal size on the CPU, the served tokens of a real run are
read once by the float32 reference (the program's number) and once with
every matrix product's inputs rounded to float8 e4m3 (the control: at
each position, the float32 gap of the token float8 puts first).  The
harness's check, with the control in the program's place, has to come
out not correct; the program's widest gap stays under the limit.  On the
chip the same readings come from ``control.py``.
"""
import json
import pathlib
import time

import pytest

from benchmarks.chip import harness

WORKLOADS = pathlib.Path(__file__).resolve().parents[1] / "workloads"


@pytest.mark.parametrize("cell", ["qwen2_5_3b.reason_decode"])
def test_control_reads_above_the_limit(cell):
    limit = json.loads((WORKLOADS / f"{cell}.json").read_text())[
        "check"]["max_logit_gap"]
    res = harness.execute(cell, 2**31 + 7, 3.0, False,
                          t_process=time.perf_counter(), rehearse=True,
                          control=True)
    program = res["_detail"]["program_max_gap"]
    control = res["checks"]["max_logit_gap"]["value"]
    assert res["_detail"]["compared_tokens"] >= 200
    assert not res["correct"], res["checks"]
    assert program <= limit < control, (program, limit, control)
