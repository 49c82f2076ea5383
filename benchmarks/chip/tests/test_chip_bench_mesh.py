"""A cell on a mesh, rehearsed on four virtual CPU devices.

``mesh_rehearsal.py`` runs in a process of its own (JAX fixes the device
count when it starts) at ``qwen2_7b.tp4_chat``'s rehearsal size: the
weights made straight into the engine's placement carry the same bits as
on one device, an unbroken run through ``run.py`` is correct and prints no
result for want of a TPU, and the float8 control in the
program's place, a token altered, a step that keeps its state and the
exchange between chips left out are each not correct.  The cell's own
harness pieces are checked here in the test process.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from benchmarks.chip import harness

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[2]
CELL = "qwen2_7b.tp4_chat"


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, str(HERE / "mesh_rehearsal.py"),
                        CELL, str(2**31 + 4005)], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_weights_have_the_same_bits(rehearsal):
    assert rehearsal["devices"] == 4
    assert rehearsal["sharded_leaves"] > 0
    assert rehearsal["same_bits"]


def test_mesh_run_is_correct(rehearsal):
    run = rehearsal["runs"]["unbroken"]
    assert run["count"] == 4
    assert run["compared_tokens"] >= 60
    assert run["correct"], run["checks"]


def test_mesh_rehearsal_runs_the_machinery_then_prints_no_result(rehearsal):
    """The four-chip cell on four virtual CPU devices through ``run.py``:
    weights made into the engine's placement, the engine on a (1, 4)
    mesh, the window and the check, then no result line because there is
    no TPU."""
    p = rehearsal["run_py"]
    assert p["rc"] == 2, p["stderr"]
    assert p["stdout"].strip() == ""
    assert "[bench] window 4.0 s closed" in p["stderr"]
    assert "check max_logit_gap:" in p["stderr"]
    assert "no TPU: no result" in p["stderr"]


def test_mesh_control_reads_above_the_limit(rehearsal):
    run = rehearsal["runs"]["control"]
    gap = run["checks"]["max_logit_gap"]
    assert not run["correct"], run["checks"]
    assert run["program_max_gap"] <= gap["limit"] < gap["value"]


@pytest.mark.parametrize("fault", ["alter_tokens", "keep_state",
                                   "drop_exchange"])
def test_mesh_broken_run_is_not_correct(rehearsal, fault):
    run = rehearsal["runs"][fault]
    gap = run["checks"]["max_logit_gap"]
    assert not run["correct"], run["checks"]
    assert gap["value"] > gap["limit"]


def test_chips_must_match_the_mesh():
    c = harness.load_cell(CELL)
    assert harness.mesh_shape(c["cell"], c["cfg"]) == (1, 4)
    assert harness.mesh_shape({"chips": 1}, {"engine": {}}) is None
    with pytest.raises(ValueError, match="mesh"):
        harness.mesh_shape({"chips": 1}, c["cfg"])
    with pytest.raises(ValueError, match="mesh"):
        harness.mesh_shape({"chips": 4}, {"engine": {}})


def test_the_placeholder_cell_stays_out_of_the_benchmark():
    """The cell's rate and limit are placeholders until a four-chip run
    sets them (its workload file says so): it is in no ``BENCHMARK.json``
    entry."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = harness.load_cell(CELL)["cell"]
    assert "placeholder" in cell
    named = [w["name"] for w in bench["workloads"]] + [
        c for m in bench["end_to_end"] + bench["per_layer"]
        for c in m.get("workloads", [])]
    assert CELL not in named
