"""Async serving pipeline unit suite (ISSUE 10).

The token bit-identity of the pipeline against the tick loop lives in the
differential matrix (tests/test_engine_differential.py -k async); this
file owns the streaming API surface: submit()/results() semantics, run()
reuse across traces, caller-side validation, error propagation out of the
scheduler thread, pipeline counters, the prefill bucket tables, and the
serve.py CLI flag-coherence validation.

Every test name carries "async" so CI's async-interpret leg picks the
whole file up with -k async.
"""
import numpy as np
import pytest

import engine_harness as H
from repro.launch.async_engine import AsyncServeEngine
from repro.launch.engine import Request, ServeEngine


def _trace(seed):
    return H.random_greedy_trace(np.random.default_rng(seed))


def test_async_streaming_submit_results():
    """The streaming surface end-to-end: submit() each request, collect
    from the results() generator, match the sync tick loop token for
    token."""
    trace = _trace(7)
    sync = H.run_trace(H.slotted_engine(), trace)
    a = H.async_engine("slotted")
    for r in H.to_requests(trace, a.tick):
        a.submit(r)
    got = {}
    for c in a.results(timeout=120.0):
        got[c.rid] = c.tokens
    assert got == sync
    assert list(a.results(timeout=0.2)) == []   # drained: terminates clean


def test_async_run_reusable_across_traces():
    """run() is a thin compat wrapper: consecutive traces on ONE wrapper
    (threads idle in between) each match the sync engine."""
    a = H.async_engine("slotted")
    for seed in (8, 9):
        trace = _trace(seed)
        assert H.run_trace(a, trace) \
            == H.run_trace(H.slotted_engine(), trace), f"seed {seed}"


def test_async_run_returns_with_the_pipeline_drained():
    """run() hands back its last completion only once the ticks still in
    flight are harvested, so the caller may drive the wrapped engine
    directly at once (the bucket-isolation differential test does): a
    late harvest of a stale tick would retire the requests admitted
    next, and a late dispatch would donate the cache under them."""
    a = H.async_engine("slotted")
    for seed in range(6):
        H.run_trace(a, _trace(seed))
        assert a._inflight == 0, f"seed {seed}"
        assert a.engine.free_slots == a.engine.max_slots


def test_async_duplicate_rid_rejected():
    trace = [((0, 1, 2), 4, 0), ((2, 1), 3, 0)]
    a = H.async_engine("slotted")
    reqs = H.to_requests(trace, a.tick)
    a.submit(reqs[0])
    dup = Request(rid=reqs[0].rid, tokens=(1,), max_new_tokens=2,
                  arrival=a.tick)
    with pytest.raises(ValueError, match="already in flight"):
        a.submit(dup)
    a.submit(reqs[1])
    assert sorted(c.rid for c in a.results(timeout=120.0)) == [0, 1]
    with pytest.raises(ValueError, match="duplicate rids"):
        a.run(H.to_requests([((0,), 2, 0), ((1,), 2, 0)], a.tick)
              + [Request(rid=0, tokens=(2,), max_new_tokens=2,
                         arrival=a.tick)])


def test_async_caller_side_validation_keeps_pipeline_clean():
    """A statically invalid request raises on the CALLER (prompt longer
    than max_len) and must not enter the pending set or poison the
    pipeline — the next good trace still serves."""
    a = H.async_engine("slotted")
    bad = Request(rid=999, tokens=tuple(range(H.MAX_LEN + 4)),
                  max_new_tokens=2, arrival=a.tick)
    with pytest.raises(ValueError):
        a.submit(bad)
    trace = _trace(13)
    assert H.run_trace(a, trace) \
        == H.run_trace(H.slotted_engine(), trace)


def test_async_scheduler_error_propagates_to_caller():
    """An exception on the scheduler thread (here: a deadlocked schedule —
    admission monkeypatched shut) must surface as RuntimeError on the next
    results()/run() call with the original error chained, never hang."""
    eng = H.slotted_engine()
    wrapper = AsyncServeEngine(eng)
    orig = eng._can_admit
    eng._can_admit = lambda waiting: False
    try:
        with pytest.raises(RuntimeError) as ei:
            wrapper.run([Request(rid=0, tokens=(0, 1), max_new_tokens=2,
                                 arrival=eng.tick)])
        assert "deadlock" in str(ei.value.__cause__)
    finally:
        # the singleton engine itself was never mutated (nothing admitted)
        eng._can_admit = orig


def test_async_drain_error_propagates():
    """An exception on the DRAIN thread is forwarded through the harvest
    queue and re-raised on the caller, with the pipeline marked failed.
    A FRESH engine: the poisoned run leaves an un-harvested slot behind,
    which must not leak into the shared singletons."""
    eng = ServeEngine(H.CFG, H.shared_params(), **H.engine_kwargs())
    wrapper = AsyncServeEngine(eng)
    # the drain thread's failure protocol: exceptions travel the harvest
    # queue as items; pre-seeding one exercises the same path
    wrapper._harvest_q.put(RuntimeError("drain died"))
    with pytest.raises(RuntimeError):
        wrapper.run([Request(rid=0, tokens=(0, 1), max_new_tokens=2,
                             arrival=eng.tick)])
    with pytest.raises(RuntimeError):
        wrapper.submit(Request(rid=1, tokens=(0,), max_new_tokens=2,
                               arrival=eng.tick))


def test_async_close_is_idempotent_and_restartable():
    a = H.async_engine("slotted")
    trace = _trace(14)
    sync = H.run_trace(H.slotted_engine(), trace)
    assert H.run_trace(a, trace) == sync
    a.close()
    a.close()
    assert H.run_trace(a, trace) == sync      # lazily restarts


def test_async_metrics_group_counters():
    a = H.async_engine("slotted")
    H.run_trace(a, _trace(15))
    st = a.metrics.snapshot()["async"]
    assert st["submitted"] == st["completed"] >= len(_trace(15)) > 0
    assert st["dispatched_ticks"] >= 1
    assert 1 <= st["max_inflight"] <= st["drain_depth"] == a.drain_depth


def test_async_drain_depth_validation():
    with pytest.raises(ValueError, match="drain_depth"):
        AsyncServeEngine(H.slotted_engine(), drain_depth=0)


def test_async_prefill_bucket_tables():
    """prefill_buckets=True builds the power-of-two chunk-count ladder up
    to ceil(max_len / prefill_chunk); an explicit iterable is sorted,
    clamped, and closed with that maximum; pad accounting is exposed."""
    n_max = -(-H.MAX_LEN // 4)                  # chunk=4 in engine_kwargs
    auto = H.async_engine("slotted").engine
    want = [1, 2, 4]
    assert auto._bucket_sizes == [b for b in want if b < n_max] + [n_max]
    explicit = H.slotted_engine(prefill_buckets=(3, 2, 99))
    assert explicit._bucket_sizes == [2, 3, n_max]
    assert explicit.aot_prefill
    trace = [((0, 1, 2, 3, 4, 5, 6, 7, 8), 3, 0)]   # 9 tok -> 3 chunks
    pad0 = explicit.prefill_pad_chunks
    assert H.run_trace(explicit, trace) \
        == H.run_trace(H.slotted_engine(), trace)
    assert explicit.prefill_pad_chunks == pad0, \
        "3 chunks must hit the exact bucket 3, no padding"
    trace = [((0, 1, 2, 3, 4), 3, 0)]               # 5 tok -> 2 chunks
    H.run_trace(explicit, trace)
    assert explicit.prefill_pad_chunks == pad0      # exact bucket 2
    trace = [((0,) * 13, 3, 0)]                     # 4 chunks -> bucket 6
    H.run_trace(explicit, trace)
    assert explicit.prefill_pad_chunks == pad0 + 2


# ---------------------------------------------------------------------------
# serve.py CLI flag coherence (ISSUE 10 satellite): incoherent combos fail
# fast with a clear argparse error instead of being silently ignored.
# All of these exit inside argument validation — no jax work happens.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("argv", [
    ["--continuous", "--paged"],
    ["--async-serve"],                       # engine flag, lockstep path
    ["--telemetry"],
    ["--metrics"],
    ["--slots", "8"],
    ["--mesh", "1,1"],
    ["--spec", "2"],                         # paged flag, lockstep path
    ["--continuous", "--spec", "2"],         # paged flag, wrong engine
    ["--continuous", "--kv-quant", "log8"],
    ["--continuous", "--priority", "2"],
    ["--mesh-rules", "serve", "--continuous"],   # rules without --mesh
    ["--profile-dir", "/tmp/x", "--continuous"],  # dir without ticks
    ["--continuous", "--python-loop"],
    ["--paged", "--batch", "2"],
    ["--paged", "--drift", "0.5"],           # drift without --spec
])
def test_async_serve_cli_rejects_incoherent_flags(argv):
    from repro.launch import serve
    with pytest.raises(SystemExit) as ei:
        serve.run(argv)
    assert ei.value.code == 2                # argparse error exit
