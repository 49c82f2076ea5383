"""The harness on the CPU: it refuses to measure, and a cell made only of
data files is found by name."""
import json
import os
import pathlib
import subprocess
import sys
import time


from benchmarks.chip import harness

HERE = pathlib.Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
RUN = HERE / "run.py"


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def _run(*extra):
    return subprocess.run(
        [sys.executable, str(RUN), "--workload", "qwen2_5_3b.chat_prefix",
         "--seed", str(2**31 + 77), "--seconds", "3", "--trace", "0",
         *extra], capture_output=True, text=True, env=_cpu_env(), cwd=REPO,
        timeout=600)


def _no_result(p):
    last = (p.stdout.strip().splitlines() or [""])[-1]
    try:
        json.loads(last)
    except json.JSONDecodeError:
        return True
    return False


def test_no_tpu_no_result():
    p = _run()
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_rehearsal_runs_the_machinery_then_prints_no_result():
    """chat_prefix's whole run (weights, engine, warm-up, open-loop
    window, reference check) on the reduced qwen2_5_3b, then no result
    line because there is no TPU."""
    p = _run("--rehearse")
    assert p.returncode == 2, p.stderr[-3000:]
    assert _no_result(p)
    assert "[bench] window 3.0 s closed" in p.stderr
    assert "check max_logit_gap:" in p.stderr
    assert "no TPU: no result" in p.stderr


def test_a_data_only_cell_is_picked_up(tmp_path):
    (tmp_path / "workloads").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "tiny_batch.json").write_text(json.dumps({
        "arrival": {"process": "backlog"},
        "sessions": {"turns_min": 1, "turns_max": 1},
        "system_prompts": None,
        "message": [{"dist": "uniform", "min": 64, "max": 256}],
        "reply": {"dist": "uniform", "min": 0, "max": 0},
        "output": {"dist": "uniform", "min": 64, "max": 128}}))
    (tmp_path / "workloads" / "qwen2_5_3b.tiny_batch.json").write_text(
        json.dumps({"config": "qwen2_5_3b", "traffic": "tiny_batch",
                    "chips": 1,
                    "check": {"max_logit_gap": 0.5, "ref_tokens": 50,
                              "ref_max_requests": 2}}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cell = "qwen2_5_3b.tiny_batch"
    for m in bench["end_to_end"]:
        if m["name"] == "output_tok_s":
            m["workloads"].append(cell)
    # a split name with no reader of its own: read by its stem's reader
    bench["per_layer"].append({
        "name": "prefill_useful_share.tiny", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "tick programs",
        "moves": "output_tok_s", "workloads": [cell]})
    for trace, want in ((False, {"output_tok_s", "setup_s"}),
                        (True, {"prefill_useful_share.tiny"})):
        res = harness.execute(cell, 5, 2.0, trace,
                              t_process=time.perf_counter(), rehearse=True,
                              roots=(tmp_path, HERE), bench=bench)
        assert set(res["metrics"]) == want
        assert res["attempted"] > 0 and res["failed"] == 0
        assert res["correct"], res["checks"]
