"""Serving observability suite (ISSUE 8, DESIGN.md §12).

Three layers of coverage:

* **obs primitives** — ring-buffer bounding with drop accounting, event
  schema enforcement at emit time, JSONL flush format, percentile math
  validated *exactly* against numpy, span totals, request-record derived
  latencies on a fake clock, registry instruments and Prometheus text.
* **engine integration** — a real paged+spec serve produces a
  schema-valid trace whose lifecycle events reconcile with the returned
  completions; per-request spec acceptance sums to the engine totals; the
  registry's group snapshots compare ``==`` to the three legacy stats
  dicts (the deprecation-shim window contract).
* **fidelity log bounding** — the ladder's event log is a ring with the
  same policy (the unbounded-growth satellite).

The on/off token-bit-identity column lives in
tests/test_engine_differential.py (``-k telemetry``).
"""
import json

import numpy as np
import pytest

import engine_harness as H
from repro.launch.fidelity import FidelityMonitor, FidelityPolicy
from repro.obs import (BoundedLog, EVENT_SCHEMA, EventTrace, MetricsRegistry,
                       Percentiles, RequestRecord, SCHEMA_VERSION, Telemetry,
                       TickProfiler)

# ---------------------------------------------------------------------------
# obs primitives
# ---------------------------------------------------------------------------


def test_bounded_log_ring_and_drop_count():
    log = BoundedLog(capacity=3)
    for i in range(7):
        log.append(i)
    assert len(log) == 3
    assert list(log) == [4, 5, 6]        # oldest fell off the far end
    assert log.dropped == 4
    log.clear()
    assert len(log) == 0 and log.dropped == 0


def test_bounded_log_rejects_degenerate_capacity():
    with pytest.raises(ValueError):
        BoundedLog(capacity=0)


def test_event_trace_enforces_schema():
    tr = EventTrace()
    rec = tr.emit("enqueue", 3, rid=7)
    assert rec["ev"] == "enqueue" and rec["tick"] == 3 and rec["seq"] == 0
    with pytest.raises(ValueError, match="unknown event kind"):
        tr.emit("nope", 0)
    with pytest.raises(ValueError, match="fields"):
        tr.emit("enqueue", 0)                       # missing rid
    with pytest.raises(ValueError, match="fields"):
        tr.emit("enqueue", 0, rid=1, extra=2)       # extra field
    # failed emits must not burn sequence numbers
    assert tr.emit("enqueue", 4, rid=8)["seq"] == 1


def test_event_trace_jsonl_flush(tmp_path):
    tr = EventTrace(capacity=2)
    for i in range(4):                   # overflow: 2 retained, 2 dropped
        tr.emit("enqueue", i, rid=i)
    path = tmp_path / "trace.jsonl"
    assert tr.flush_jsonl(path) == 2
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    meta, events = lines[0], lines[1:]
    assert meta == {"ev": "meta", "schema_version": SCHEMA_VERSION,
                    "events": 2, "dropped": 2}
    assert [e["rid"] for e in events] == [2, 3]
    for e in events:
        assert set(e) == {"ev", "t", "tick", "seq", *EVENT_SCHEMA[e["ev"]]}
    # flush observes, it does not consume
    assert len(tr) == 2


def test_percentiles_match_numpy_exactly():
    rng = np.random.default_rng(5)
    vals = rng.exponential(size=200)
    p = Percentiles(window=4096)         # under the window: exact
    for v in vals:
        p.add(v)
    s = p.summary()
    assert s["count"] == 200
    for q in (50, 90, 99):
        assert s[f"p{q}"] == float(np.percentile(vals, q))
    assert s["max"] == float(vals.max())
    assert np.isclose(s["mean"], vals.mean())


def test_percentiles_sliding_window_keeps_freshest():
    p = Percentiles(window=10)
    for v in range(100):
        p.add(float(v))
    s = p.summary()
    assert s["count"] == 100             # lifetime count survives the slide
    assert s["p50"] == float(np.percentile(np.arange(90, 100), 50))
    assert p.summary()["max"] == 99.0
    p.reset()
    assert p.summary() == {"count": 0, "mean": None, "max": None,
                           "p50": None, "p90": None, "p99": None}


def test_percentiles_buffer_bounded_with_bench_scale_equality():
    """Satellite (ISSUE 10): the percentile state must hold at most
    ``window`` floats no matter how long the serve runs, and at bench
    scale (n <= window — every BENCH_serve latency cell) the bounded
    summary equals unbounded ``np.percentile`` EXACTLY, so the committed
    p50/p90/p99 baselines are untouched by the bound."""
    p = Percentiles()
    rng = np.random.default_rng(9)
    vals = rng.exponential(size=3000)        # bench cells sit well under
    for v in vals:                           # the 4096 default window
        p.add(v)
    assert p._vals.maxlen == p.window == 4096
    assert len(p._vals) == 3000
    s = p.summary()
    for q in (50, 90, 99):
        assert s[f"p{q}"] == float(np.percentile(vals, q))
    # multi-hour serve: memory stays flat at the window, summaries track
    # the freshest window exactly
    more = rng.exponential(size=20_000)
    for v in more:
        p.add(v)
    assert len(p._vals) == p.window
    assert p.count == 23_000                 # lifetime accounting survives
    tail = np.concatenate([vals, more])[-p.window:]
    for q in (50, 90, 99):
        assert p.summary()[f"p{q}"] == float(np.percentile(tail, q))


def test_obs_accumulators_are_thread_safe():
    """The async pipeline's drain thread closes spans while the scheduler
    thread emits events and percentiles, and readers snapshot mid-serve:
    hammer every accumulator from threads and assert nothing is lost (an
    unlocked dict read-modify-write could drop updates at bytecode
    boundaries), and that each thread's spans nest on its own stack."""
    import sys
    import threading
    perc = Percentiles(window=128)
    trace = EventTrace(capacity=256)
    N, T = 2000, 6
    start = threading.Barrier(T)
    bad = []
    opened = {}                              # harvest id -> its drain's id

    def hammer(i):
        start.wait()
        for k in range(N):
            with trace.span("drain", k) as outer:
                perc.add(float(k))
                with trace.span("harvest", k, rids=[i],
                                n_emitted=[k]) as inner:
                    opened[inner.id] = outer.id
                    if trace.current() == outer.id:
                        bad.append(i)        # not on this thread's stack
            if k % 256 == 0:                 # concurrent readers
                trace.totals()
                perc.summary()

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(T)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(switch)
    assert not bad
    totals = trace.totals()
    assert totals["drain"]["calls"] == totals["harvest"]["calls"] == T * N
    assert perc.count == T * N
    assert np.isclose(perc.total, T * sum(range(N)))
    assert len(trace) + trace.dropped == 2 * T * N
    seqs = [e["seq"] for e in trace]
    assert len(set(seqs)) == len(seqs), "racing emits burned a seq twice"
    ids = [e["span"] for e in trace]
    assert len(set(ids)) == len(ids), "racing spans shared an id"
    for e in trace:
        if e["ev"] == "harvest":
            # its own thread's drain (ids interleave across threads)
            assert e["parent"] == opened[e["span"]]


def test_phase_timers_accumulate():
    """Span totals on a fake clock: each closed span folds t - t0 into its
    kind's lifetime seconds and calls, which ``summary()["phases"]``
    reports in the shape the old phase timers had."""
    clock = iter([0.0, 1.5, 2.0, 2.25, 3.0, 3.5, 4.0]).__next__
    tel = Telemetry(clock=clock)
    with tel.trace.span("dispatch", 0):          # 0.0 -> 1.5
        pass
    tick = tel.trace.open("decode_block", 0, n_active=1, block=2,
                          kv_blocks_read=None, kv_blocks_view=None)  # 2.0
    with tel.trace.span("dispatch", 0, parent=tick.id):   # 2.25 -> 3.0
        pass
    tel.trace.close(tick)                         # 3.5
    snap = tel.summary()["phases"]
    assert snap["dispatch"]["calls"] == 2
    assert np.isclose(snap["dispatch"]["seconds"], 2.25)
    assert snap["decode_block"] == {"seconds": 1.5, "calls": 1}
    ev = list(tel.trace)
    assert [e["ev"] for e in ev] == ["dispatch", "dispatch", "decode_block"]
    assert ev[0]["parent"] is None and ev[1]["parent"] == tick.id
    assert (ev[2]["t0"], ev[2]["t"]) == (2.0, 3.5)
    with pytest.raises(RuntimeError):
        with tel.trace.span("merge", 0):          # 4.0: a failed phase
            raise RuntimeError("boom")
    assert "merge" not in tel.summary()["phases"]
    assert tel.trace.current() is None           # its stack entry is gone
    tel.close()


def test_request_record_derived_latencies():
    r = RequestRecord(rid=1, enqueue_s=10.0, enqueue_tick=0)
    assert r.ttft_s is None and r.tpot_s is None and r.queue_wait_s is None
    r.admit_s, r.admit_tick = 10.5, 4
    r.first_token_s = 10.75
    r.finish_s, r.finish_tick = 12.75, 9
    r.n_tokens, r.drafted, r.accepted = 5, 8, 6
    assert r.queue_wait_s == 0.5 and r.queue_wait_ticks == 4
    assert r.ttft_s == 0.75
    assert r.tpot_s == 2.0 / 4           # (finish - first) / (n - 1)
    assert r.acceptance == 0.75
    r.n_tokens = 1
    assert r.tpot_s == 0.0               # single-token: no inter-token gap


def test_telemetry_lifecycle_on_fake_clock():
    clock = iter(np.arange(0.0, 100.0, 0.5)).__next__
    tel = Telemetry(clock=clock)
    tel.enqueue(1, tick=0)
    tel.admit(1, tick=2, slot=0, prompt_len=4)
    tel.first_token(1, tick=2)
    tel.finish(1, tick=8, reason="length", n_tokens=3)
    s = tel.summary()
    assert s["requests_finished"] == 1 and s["inflight"] == 0
    assert s["ttft_s"]["count"] == 1 and s["queue_wait_s"]["count"] == 1
    # admit with no prior enqueue synthesizes the record (bench drivers
    # call _admit_wave directly); duplicate finish is ignored
    tel.admit(9, tick=4, slot=1, prompt_len=2)
    tel.finish(9, tick=5, reason="eos", n_tokens=1)
    tel.finish(9, tick=5, reason="eos", n_tokens=1)
    assert tel.summary()["requests_finished"] == 2
    kinds = [e["ev"] for e in tel.trace]
    assert kinds.count("finish") == 2
    tel.reset()
    assert len(tel.trace) == 0
    assert tel.summary()["requests_finished"] == 0


def test_tick_profiler_validates():
    with pytest.raises(ValueError):
        TickProfiler("/tmp/x", 0)
    p = TickProfiler(None, 2)
    assert p.logdir and not p.active and not p.done


def test_tick_profiler_start_failure_raises(monkeypatch):
    """A profiler that cannot start fails the run instead of silently
    leaving it without the trace it asked for."""
    import jax

    def broken(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", broken)
    p = TickProfiler(None, 2)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        p.tick()
    assert not p.active


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("ticks")
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(3)
    g.dec()
    assert g.snapshot() == 2
    lazy = reg.gauge("lazy", fn=lambda: 42)
    assert lazy.snapshot() == 42
    with pytest.raises(ValueError, match="duplicate"):
        reg.counter("ticks")
    with pytest.raises(ValueError, match="identifier"):
        reg.counter("bad-name")


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 5
    assert np.isclose(snap["sum"], 56.05)
    assert snap["buckets"] == {0.1: 1, 1.0: 3, 10.0: 4}   # cumulative (le)
    with pytest.raises(ValueError, match="increasing"):
        reg.histogram("bad", buckets=(1.0, 0.5))


def test_registry_snapshot_and_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("reqs", "requests served").inc(7)
    reg.register_group("pool", lambda: {"hits": 3, "miss_rate": 0.25,
                                        "tag": "ignored", "flag": True})
    snap = reg.snapshot()
    assert snap["pool"]["hits"] == 3
    assert snap["metrics"]["reqs"] == 7
    text = reg.prometheus_text()
    assert "# TYPE nldpe_reqs counter" in text
    assert "nldpe_reqs 7" in text
    assert "nldpe_pool_hits 3" in text
    assert "nldpe_pool_miss_rate 0.25" in text
    assert "tag" not in text             # non-numeric leaves are skipped
    assert "flag" not in text            # bools are not gauges
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# engine integration: trace validity + registry shim + acceptance splits
# ---------------------------------------------------------------------------


def _served_telemetry():
    """One served COW/eviction trace on the instrumented spec singleton
    (module-cached by the harness; telemetry reset for a clean window)."""
    eng = H.paged_engine(spec_k=2, telemetry=True)
    eng.telemetry.reset()
    trace = H.shared_prefix_cow_trace(seed=23)
    outs = H.run_trace(eng, trace)
    H.audit(eng)
    return eng, trace, outs


def test_engine_trace_is_schema_valid_jsonl(tmp_path):
    eng, trace, outs = _served_telemetry()
    path = tmp_path / "serve.jsonl"
    n = eng.telemetry.flush_jsonl(path)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    meta, events = lines[0], lines[1:]
    assert meta["schema_version"] == SCHEMA_VERSION
    assert len(events) == n
    seqs = []
    for e in events:
        assert set(e) == {"ev", "t", "tick", "seq",
                          *EVENT_SCHEMA[e["ev"]]}, e
        seqs.append(e["seq"])
    assert seqs == sorted(seqs)          # monotone (gaps = ring drops only)
    # lifecycle reconciliation: every request's four edges, in causal order
    n_reqs = len(trace)
    for ev, want in (("enqueue", n_reqs), ("admit", n_reqs),
                     ("first_token", n_reqs), ("finish", n_reqs)):
        assert sum(e["ev"] == ev for e in events) == want, ev
    by_rid = {rid: {e["ev"]: e for e in events if e.get("rid") == rid}
              for rid in outs}
    for rid, toks in outs.items():
        edges = by_rid[rid]
        assert edges["finish"]["n_tokens"] == len(toks)
        assert (edges["enqueue"]["t"] <= edges["admit"]["t"]
                <= edges["first_token"]["t"] <= edges["finish"]["t"])
        assert edges["finish"]["ttft_s"] >= 0
        assert edges["finish"]["pages_held"] > 0
        assert edges["admit"]["prompt_len"] == len(trace[rid][0])


def test_engine_per_request_acceptance_sums_to_totals():
    eng, trace, outs = _served_telemetry()
    recs = list(eng.telemetry.records)
    assert len(recs) == len(trace)
    # windowed engine totals were NOT reset — compare within the window:
    # each record's drafted/accepted is a slot-counter delta, so the sum
    # over this trace's records equals the spec_stats delta it produced
    drafted = sum(r.drafted for r in recs)
    accepted = sum(r.accepted for r in recs)
    assert drafted > 0
    assert 0 <= accepted <= drafted
    for r in recs:
        assert 0 <= r.accepted <= r.drafted
        assert r.acceptance is None or 0.0 <= r.acceptance <= 1.0
        assert r.n_tokens == len(outs[r.rid])
        assert r.pages_held >= 1
        assert r.queue_wait_ticks >= 0
    s = eng.telemetry.summary()
    assert s["ttft_s"]["count"] == len(trace)
    assert s["tpot_s"]["p99"] is not None
    for phase in ("admission_wave", "spec_draft", "spec_verify"):
        assert s["phases"][phase]["seconds"] > 0, phase


def test_registry_supersedes_legacy_stats_dicts():
    """The deprecation-shim window: one snapshot() serves byte-equal views
    of the three legacy dicts, so dashboards migrate with no value drift."""
    eng, _, _ = _served_telemetry()
    snap = eng.metrics.snapshot()
    assert snap["pool"] == eng.stats
    assert snap["spec"] == eng.spec_stats
    assert snap["fidelity"] == eng.fidelity_stats
    assert snap["engine"]["free_slots"] == eng.max_slots
    assert snap["latency"]["requests_finished"] >= 1
    text = eng.metrics.prometheus_text()
    assert f"nldpe_spec_drafted {eng.spec_stats['drafted']}" in text
    assert f"nldpe_pool_evicted {eng.stats['evicted']}" in text


def test_slotted_engine_registry_and_trace():
    eng = H.slotted_engine(telemetry=True)
    eng.telemetry.reset()
    H.run_trace(eng, [((0, 1, 2), 4, 0), ((1, 1), 3, 2)])
    snap = eng.metrics.snapshot()
    assert "pool" not in snap            # no paged groups on the base engine
    assert snap["latency"]["requests_finished"] == 2
    kinds = {e["ev"] for e in eng.telemetry.trace}
    assert {"enqueue", "admit", "first_token", "finish",
            "admission_wave", "decode_block"} <= kinds
    for e in eng.telemetry.trace:
        if e["ev"] == "decode_block":
            assert e["t"] >= e["t0"] and e["block"] == eng.decode_block


def test_spec_draft_seconds_uses_monotonic_clock():
    """The satellite fix: draft metering must ride time.perf_counter —
    an NTP step of time.time() can never produce a negative phase.  Guard
    the source, not the symptom (a step during CI is not reproducible)."""
    import inspect
    import re
    from repro.launch import engine as E
    # the spec tick body moved into _dispatch_tick (ISSUE 10 async split)
    src = inspect.getsource(E.PagedServeEngine._dispatch_tick)
    assert not re.search(r"=\s*time\.time\(\)", src)
    assert "perf_counter" in src
    eng, _, _ = _served_telemetry()
    assert eng.spec_draft_seconds >= 0
    assert eng.telemetry.summary()["phases"]["spec_draft"]["seconds"] >= 0


# ---------------------------------------------------------------------------
# fidelity event-log bounding (satellite) + ladder events in the trace
# ---------------------------------------------------------------------------


def test_fidelity_event_log_is_bounded():
    pol = FidelityPolicy(window=1, event_log_cap=4)
    mon = FidelityMonitor(pol, spec_k=4)
    # all-bad windows walk the reprogram -> reprogram -> disable ladder;
    # re-arm by hand after each disable so events keep coming (the ring
    # cap is the test subject, not the ladder)
    for i in range(64):
        mon.observe(drafted=4, accepted=0, t=float(i), tick=i)
        if mon.disabled:
            mon.disabled = False
            mon.spec_k = pol.min_spec_k
            mon._failed_reprograms = 0
    assert len(mon.events) <= 4
    assert mon.events.dropped > 0
    with pytest.raises(ValueError, match="event_log_cap"):
        FidelityPolicy(event_log_cap=0)


def test_fidelity_ladder_events_reach_telemetry():
    """A degrading drift engine with telemetry emits schema-valid
    'fidelity' events mirroring the monitor's ladder log, and
    fidelity_stats reports the ring's drop count."""
    eng = H.drift_engine(spec_k=2, nu=1.2, t0=1.0, dt_step=50.0,
                         fidelity=FidelityPolicy(window=2),
                         telemetry=True)
    rng = np.random.default_rng(3)
    trace = [(tuple(int(x) for x in rng.integers(0, 3, 5)), 6,
              int(rng.integers(0, 2))) for _ in range(8)]
    H.run_trace(eng, trace)
    ladder = [e for e in eng.telemetry.trace if e["ev"] == "fidelity"]
    assert len(ladder) > 0, "drift this severe must move the ladder"
    assert len(ladder) == len(list(eng.monitor.events))
    for e, me in zip(ladder, eng.monitor.events):
        assert e["kind"] == me["event"]
        assert set(e) == {"ev", "t", "tick", "seq",
                          *EVENT_SCHEMA["fidelity"]}
    assert eng.fidelity_stats["events_dropped"] == 0
