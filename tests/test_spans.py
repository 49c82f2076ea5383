"""Spans inside the serving loop (DESIGN.md §12).

* the span tree an async paged serve leaves: every parent exists and
  encloses its child in time and on its thread (the decode tick alone
  spans threads), a request's lifecycle events and spans share its rid,
  and a compile is counted and parented to the span that triggered it;
* the spans as ``nldpe.<kind>`` annotations on a host plane of a
  ``jax.profiler`` trace;
* program names that stay ``jit_decode`` / ``jit_chunk`` under a mesh.
"""
import glob
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np

import engine_harness as H
from repro.launch.async_engine import AsyncServeEngine
from repro.launch.engine import PagedServeEngine, Request
from repro.obs import Telemetry


def _requests(rng, n, base=0):
    return [Request(rid=base + i,
                    tokens=tuple(int(x) for x in rng.integers(
                        0, H.CFG.vocab_size, int(rng.integers(3, 12)))),
                    max_new_tokens=int(rng.integers(3, 9)))
            for i in range(n)]


def _served_tree():
    """A fresh telemetry-on paged engine (three slots: shapes no other
    suite compiles, so its programs compile while the telemetry watches)
    behind the async pipeline; returns the events, the thread each was
    emitted on (by seq), the telemetry summary and the completions."""
    tel = Telemetry(capacity=1 << 14)
    threads = {}
    emit = tel.trace._emit

    def recording(ev, tick, t, fields, t0=None):
        rec = emit(ev, tick, t, fields, t0)
        threads[rec["seq"]] = threading.get_ident()
        return rec

    tel.trace._emit = recording
    eng = PagedServeEngine(H.CFG, H.shared_params(), telemetry=tel,
                           **H.engine_kwargs(max_slots=3, page_size=H.PAGE,
                                             num_pages=3 * 6))
    runner = AsyncServeEngine(eng, drain_depth=2)
    try:
        comps = runner.run(_requests(np.random.default_rng(11), 7))
    finally:
        runner.close()
        tel.close()
    return list(tel.trace), threads, tel.summary(), comps


def test_span_tree_is_consistent():
    events, threads, summary, comps = _served_tree()
    assert events[0]["seq"] == 0 and summary["events_dropped"] == 0
    spans = {e["span"]: e for e in events if "span" in e}
    assert len(spans) == sum("span" in e for e in events)
    kinds = {e["ev"] for e in spans.values()}
    assert {"admission", "flush", "admission_wave", "plan", "prefill",
            "first_token_sync", "merge", "decode_block", "dispatch",
            "drain", "harvest"} <= kinds
    for e in events:
        if e.get("parent") is None:
            continue
        p = spans[e["parent"]]
        start = e.get("t0", e["t"])
        assert p["t0"] <= start <= e["t"] <= p["t"], (p, e)
        if p["ev"] != "decode_block":           # the one cross-thread span
            assert threads[p["seq"]] == threads[e["seq"]], (p, e)
    # a decode tick has one dispatch, one drain and one harvest, and the
    # tick spans threads: drain runs apart from dispatch and harvest
    ticks = [e for e in spans.values() if e["ev"] == "decode_block"]
    assert ticks
    for tick in ticks:
        kids = sorted(c["ev"] for c in spans.values()
                      if c["parent"] == tick["span"])
        assert kids == ["dispatch", "drain", "harvest"], kids
    # the wave's parts sit inside it, its parent is the scheduler's pass
    for w in (e for e in spans.values() if e["ev"] == "admission_wave"):
        assert spans[w["parent"]]["ev"] == "admission"
        kids = {c["ev"] for c in spans.values() if c["parent"] == w["span"]}
        assert {"plan", "prefill", "first_token_sync"} <= kids
        assert w["n_reqs"] == len(w["rids"]) and w["n_chunks"] >= 1
    # one request's events and spans share its rid, and the harvests'
    # emission stamps add up to its tokens
    for c in comps:
        by = [e["ev"] for e in events if e.get("rid") == c.rid]
        assert by[:2] == ["submit", "enqueue"] and by[-1] == "finish", by
        waves = [e for e in spans.values() if e["ev"] == "admission_wave"
                 and c.rid in e["rids"]]
        assert len(waves) == 1
        got = [e["n_emitted"][e["rids"].index(c.rid)]
               for e in spans.values()
               if e["ev"] == "harvest" and c.rid in e["rids"]]
        assert got and 1 + sum(got) == len(c.tokens)
    # compiles: counted, and each one inside a span names it as parent
    compiles = [e for e in events if e["ev"] == "compile"]
    assert summary["compiles"] == sum(e["stage"] == "backend_compile"
                                      for e in compiles) > 0
    parented = [spans[e["parent"]]["ev"] for e in compiles
                if e["parent"] is not None]
    assert "prefill" in parented and "dispatch" in parented, parented
    # summary phases fold the closed spans
    ph = summary["phases"]
    assert ph["decode_block"]["calls"] == len(ticks)
    assert ph["harvest"]["calls"] == len(ticks)


def test_spans_are_annotations_on_a_host_plane(tmp_path):
    from jax.profiler import ProfileData
    a = H.async_engine("paged", telemetry=True)
    reqs = _requests(np.random.default_rng(3), 3, base=500)
    a.run(_requests(np.random.default_rng(3), 3, base=400))   # warm
    with jax.profiler.trace(str(tmp_path)):
        a.run(reqs)
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"nldpe.admission_wave", "nldpe.dispatch",
            "nldpe.harvest", "nldpe.drain"} <= names, sorted(
        n for n in names if n.startswith("nldpe."))


def _module(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _tick_programs(eng):
    s, c = eng.max_slots, eng.prefill_chunk
    vec = jax.ShapeDtypeStruct((s,), jnp.int32)
    decode = eng._decode_fn.lower(eng.params, eng.cache, eng._tok, eng._pos,
                                  eng._active, eng._gen_left, eng._temp,
                                  eng._topk, eng._keys)
    chunk = eng._chunk_fn.lower(eng.params, eng.cache,
                                jax.ShapeDtypeStruct((s, c), jnp.int32), vec,
                                jax.ShapeDtypeStruct((s,), jnp.bool_), vec)
    return _module(decode), _module(chunk)


def test_program_names_are_stable_under_a_mesh():
    """The mesh wrapper keeps the tick functions' names, so a device
    trace reads ``jit_decode`` and ``jit_chunk`` with a mesh too."""
    meshed = H.paged_engine(mesh_shape=(1, 1))
    assert meshed.mesh is not None
    assert _tick_programs(meshed) == ("jit_decode", "jit_chunk")
    assert _tick_programs(H.paged_engine()) == ("jit_decode", "jit_chunk")
