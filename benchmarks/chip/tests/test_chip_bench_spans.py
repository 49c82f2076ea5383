"""The span readers on a CPU rehearsal of an open-loop chat cell: each
reads a value, and the chat TTFT splits into its four parts."""
import json
import pathlib
import time

from benchmarks.chip import harness, spans

HERE = pathlib.Path(__file__).resolve().parents[1]
REPO = HERE.parents[1]
CHAT = ("ingest_lag_p90_ms", "sched_wait_p90_ms", "admit_wave_p90_ms",
        "decode_stall_share.chat")


def test_chat_ttft_splits_into_span_parts(tmp_path):
    (tmp_path / "workloads").mkdir()
    cell = "qwen2_5_3b.tiny_chat"
    # the chat_prefix mix at a rate that puts a few requests in the window
    (tmp_path / "workloads" / f"{cell}.json").write_text(json.dumps({
        "config": "qwen2_5_3b", "traffic": "chat_prefix", "chips": 1,
        "arrival": {"rate_req_s": 3.0},
        "check": {"max_logit_gap": 0.5, "ref_tokens": 50,
                  "ref_max_requests": 2}}))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in CHAT or m["name"] == "host_tick_ms.batch":
            m["workloads"].append(cell)
    res = harness.execute(cell, 2**31 + 5, 3.0, True,
                          t_process=time.perf_counter(), rehearse=True,
                          roots=(tmp_path, HERE), bench=bench, check=False)
    run = res["_detail"]["run"]
    assert res["attempted"] > 1 and res["failed"] == 0
    assert set(CHAT) | {"host_tick_ms.batch"} <= set(res["metrics"])
    share = res["metrics"]["decode_stall_share.chat"]["value"]
    assert 0.0 <= share <= 100.0
    parts = spans.ttft_parts(run)
    assert sorted(parts) == sorted(run.due_in_window())
    for rid, (late, ingest, wait, wave) in parts.items():
        assert min(ingest, wait, wave) >= 0.0, (rid, parts[rid])
        ttft = run.records[rid].first_token_s - run.due[rid]
        assert abs(late + ingest + wait + wave - ttft) < 1e-3, (
            rid, parts[rid], ttft)


def test_span_readers_read_nothing_from_a_cut_or_older_trace():
    """A ring that dropped events, and a program without spans (the
    schema v2 events: no submit, a wave without ``t0``), read as
    None."""
    class Run:
        events = [{"ev": "enqueue", "t": 1.0, "tick": 0, "seq": 5,
                   "rid": 1}]
    assert spans.events(Run, "enqueue") is None
    Run.events = [{"ev": "admission_wave", "t": 2.0, "tick": 0, "seq": 0,
                   "n_reqs": 1, "n_chunks": 1, "wall_s": 0.5}]
    assert spans.waves(Run) is None and spans.ttft_parts(Run) is None
    Run.due, Run.window, Run.harvests = {}, (0.0, 1.0), []
    for name in CHAT + ("host_tick_ms.batch",):
        assert harness.read_metric(name, Run, (HERE,)) is None, name
