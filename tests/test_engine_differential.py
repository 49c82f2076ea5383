"""Cross-engine differential fuzz suite (ISSUE 4 satellite + acceptance).

ONE trace runner asserts, request for request:

    lockstep run-alone == ServeEngine == PagedServeEngine
                       == PagedServeEngine(spec_k in {1, 2, 4})

token-for-token under greedy — on random Poisson traces over a tiny token
alphabet (dense shared prefixes -> radix hits and COW forks) against a
zero-headroom page pool (constant LRU eviction).  Every future engine
variant gets the full trace-equivalence battery by being added to
ENGINES() below.

The seeded np.random traces below run everywhere (hypothesis is an
optional dev dep — importorskip would silence the acceptance criterion on
hosts without it); when hypothesis IS present, the @given variants fuzz
the same runner with minimized counterexamples.

Sampled requests (temperature > 0) are *distribution*-equivalent, not
draw-equivalent, between spec and non-spec (tests/test_spec_sampling.py
carries the chi-square proof); here they must still be trace-invariant —
identical tokens whatever the submission order or co-tenants — and must
never perturb greedy co-tenants.

``NLDPE_SPEC_KS`` bounds the speculative depths tested (CI's
spec-interpret leg sets ``2``: the full matrix under the Pallas
interpreter would dominate the leg's budget).
"""
import os

import numpy as np
import pytest

import engine_harness as H
from repro.launch.engine import Request

try:
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:                     # optional dev dep; degrade
    HAVE_HYPOTHESIS = False

SPEC_KS = [int(k) for k in
           os.environ.get("NLDPE_SPEC_KS", "1,2,4").split(",")]


def ENGINES():
    """The engine matrix under differential test (greedy contract)."""
    return [("slotted", H.slotted_engine()),
            ("paged", H.paged_engine())] + [
            (f"spec{k}", H.paged_engine(spec_k=k)) for k in SPEC_KS]


# seeded trace generators live in engine_harness (shared with the sharded
# differential driver, tests/sharded_driver.py)
random_greedy_trace = H.random_greedy_trace
random_mixed_trace = H.random_mixed_trace


# ---------------------------------------------------------------------------
# the trace runners (shared by the seeded and the hypothesis variants)
# ---------------------------------------------------------------------------

def check_greedy_trace(trace):
    outs = {}
    for name, eng in ENGINES():
        outs[name] = H.run_trace(eng, trace)
        if hasattr(eng, "pool"):
            H.audit(eng)
    for rid, (prompt, gen, _) in enumerate(trace):
        alone = H.run_alone(tuple(prompt), gen)
        for name, out in outs.items():
            assert out[rid] == alone, \
                f"{name} rid {rid} diverged from the run-alone oracle"


def check_mixed_trace(trace):
    """slotted == paged bit-exactly on every request; the speculative
    engine matches them on every *greedy* request; and the speculative
    engine is trace-invariant — the same requests in reverse submission
    order reproduce every output, sampled ones included."""
    slotted = H.run_trace(H.slotted_engine(), trace)
    paged = H.run_trace(H.paged_engine(), trace)
    assert slotted == paged
    spec = H.paged_engine(spec_k=SPEC_KS[0])
    out_a = H.run_trace(spec, trace)
    for rid, t in enumerate(trace):
        if t[3] <= 0:               # greedy request
            assert out_a[rid] == slotted[rid], \
                f"speculation changed greedy rid {rid}"
        assert all(0 <= tok < H.CFG.vocab_size for tok in out_a[rid])
    reqs = H.to_requests(trace, spec.tick)
    rev = [Request(rid=r.rid, tokens=r.tokens,
                   max_new_tokens=r.max_new_tokens, temperature=r.temperature,
                   top_k=r.top_k, seed=r.seed, arrival=spec.tick)
           for r in reversed(reqs)]
    out_b = {c.rid: c.tokens for c in spec.run(rev)}
    assert out_a == out_b, "speculative sampling is not trace-invariant"
    H.audit(spec)


# ---------------------------------------------------------------------------
# seeded fuzz: runs everywhere
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_random_greedy_traces_all_engines_agree(seed):
    check_greedy_trace(random_greedy_trace(np.random.default_rng(seed)))


@pytest.mark.parametrize("seed", [10, 11])
def test_random_mixed_traces_contracts(seed):
    check_mixed_trace(random_mixed_trace(np.random.default_rng(seed)))


def test_shared_prefix_cow_eviction_trace():
    """Deterministic acceptance-criterion trace: repeated identical prompts
    (COW forks), page-multiple prompt lengths, and enough distinct long
    prompts to force eviction in the zero-headroom pool — spec output must
    stay bit-equal to non-spec paged at every tested depth, with no page
    leaks."""
    trace = H.shared_prefix_cow_trace()
    base = H.paged_engine()
    out_base = H.run_trace(base, trace)
    H.audit(base)
    assert base.stats["hits"] >= 1
    for spec_k in SPEC_KS:
        spec = H.paged_engine(spec_k=spec_k)
        out_spec = H.run_trace(spec, trace)
        assert out_spec == out_base, f"spec_k={spec_k} diverged"
        H.audit(spec)
        assert spec.spec_stats["drafted"] > 0


def test_spec_engine_through_paged_kernel(monkeypatch):
    """NLDPE_PAGED_KERNEL=1 routes the q_len = spec_k+1 verify chunk (and
    the drafts' decode steps) through the Pallas paged-attention kernel.
    Float-tolerance, not bitwise — but greedy argmax over well-separated
    logits must still emit the slotted oracle's tokens (the PR 3 decode
    opt-in test, extended to the multi-query grid)."""
    monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    rng = np.random.default_rng(29)
    trace = [(tuple(int(x) for x in rng.integers(0, H.CFG.vocab_size,
                                                 int(rng.integers(1, 9)))),
              int(rng.integers(2, 6)), int(rng.integers(0, 3)))
             for _ in range(4)]
    # a distinct singleton key: its jits must trace (and so read the env
    # var) inside this test, not reuse a dense-path compilation
    spec = H.paged_engine(spec_k=2, eos_id=-2)
    slotted = H.run_trace(H.slotted_engine(), trace)
    out = H.run_trace(spec, trace)
    assert out == slotted
    H.audit(spec)


def test_eos_truncation_matches_non_spec():
    """Mid-speculation eos: accepted drafts past the first eos must be
    dropped (never emitted, never committed) and the finish reason must
    match non-speculative decode exactly."""
    prompt = (0, 1, 2)
    alone = H.run_alone(prompt, 6)
    eos = alone[2]                      # fires on the third generated token
    base = H.paged_engine(eos_id=eos)
    spec = H.paged_engine(spec_k=2, eos_id=eos)
    reqs = H.to_requests([(prompt, 6, 0)], base.tick)
    a = {c.rid: (c.tokens, c.finish_reason) for c in base.run(reqs)}
    reqs = H.to_requests([(prompt, 6, 0)], spec.tick)
    b = {c.rid: (c.tokens, c.finish_reason) for c in spec.run(reqs)}
    assert a == b
    assert a[0][1] == "eos"
    H.audit(spec)


def test_spec_stats_expose_acceptance():
    spec = H.paged_engine(spec_k=SPEC_KS[-1])
    H.run_trace(spec, [((0, 1, 2), 6, 0)])
    st = spec.spec_stats
    for key in ("spec_steps", "drafted", "accepted", "acceptance_rate",
                "drafted_by_slot", "accepted_by_slot"):
        assert key in st
    assert 0.0 <= st["acceptance_rate"] <= 1.0
    assert sum(st["drafted_by_slot"]) == st["drafted"]


# ---------------------------------------------------------------------------
# the kv_quant column (ISSUE 7): engines over 8-bit quantized page pools
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["log8", "int8"])
def test_kv_quant_column_all_engines_agree(mode):
    """Paged+quant is bit-identical to slotted+quant token-for-token (both
    engines run the same quantize-on-write / kv_decode-on-read formulas, so
    the layout — pool vs slots — must not leak into tokens), and a
    speculative engine over the quantized pool matches them exactly (the
    verify chunk reads the same quantized cache sequential decode wrote)."""
    for seed in (0, 3):
        trace = random_greedy_trace(np.random.default_rng(seed))
        slotted = H.run_trace(H.slotted_engine(kv_quant=mode), trace)
        paged = H.paged_engine(kv_quant=mode)
        assert H.run_trace(paged, trace) == slotted, \
            f"paged+{mode} diverged from slotted+{mode}"
        H.audit(paged)
        spec = H.paged_engine(spec_k=SPEC_KS[0], kv_quant=mode)
        assert H.run_trace(spec, trace) == slotted, \
            f"spec+{mode} diverged from slotted+{mode}"
        H.audit(spec)


def test_kv_quant_pools_key_distinct_radix_roots():
    """An fp pool and a quantized pool (and the two quantized grids) carry
    different bytes for the same prompt — their engines must fingerprint
    different radix roots, so prefix pages never cross-hit."""
    fps = {mode: H.paged_engine(kv_quant=mode)._fp
           for mode in (None, "log8", "int8")}
    assert len(set(fps.values())) == 3, fps


def test_kv_quant_grid_error_bound_contract():
    """The committed per-element contract of the log8 grid (DESIGN.md §11):
    |decode(encode(x)) - x| <= max(KV_LOG8_REL_ERR * |x|,
    KV_LOG8_FLUSH * absmax) — half a log step of relative error above the
    flush threshold, absolute flush-to-zero below it."""
    from repro.core.quantization import (KV_LOG8_FLUSH, KV_LOG8_REL_ERR,
                                         kv_decode)
    from repro.nn.attention import _quantize_kv
    rng = np.random.default_rng(11)
    x = rng.normal(size=(3, 2, 8, 16)).astype(np.float32)
    x[0, 0, 0, :4] = [0.0, 1e-7, -1e-6, 1e-5]       # sub-flush magnitudes
    q, s = _quantize_kv(x, "log8")
    xr = np.asarray(kv_decode(q, s, "log8"))
    err = np.abs(xr - x)
    bound = np.maximum(KV_LOG8_REL_ERR * np.abs(x),
                       KV_LOG8_FLUSH * np.asarray(s)[..., None])
    assert (err <= bound * (1 + 1e-5)).all(), float((err / bound).max())
    assert (xr[0, 0, 0, :4] == 0).all()             # flushed exactly to 0


def test_kv_quant_engine_through_paged_kernel(monkeypatch):
    """NLDPE_PAGED_KERNEL=1 + kv_quant: decode, chunk prefill, and the
    spec-verify staircase all stream int8 pages through the Pallas kernel
    (dequant per page tile in VMEM) — tokens must still match the slotted
    quantized oracle on well-separated greedy logits."""
    monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    rng = np.random.default_rng(31)
    trace = [(tuple(int(x) for x in rng.integers(0, H.CFG.vocab_size,
                                                 int(rng.integers(1, 9)))),
              int(rng.integers(2, 6)), int(rng.integers(0, 3)))
             for _ in range(4)]
    slotted = H.run_trace(H.slotted_engine(kv_quant="log8"), trace)
    # distinct singleton keys: these engines' jits must trace (and read
    # the env var) inside this test, not reuse a dense-path compilation
    for eng in (H.paged_engine(kv_quant="log8", eos_id=-3),
                H.paged_engine(spec_k=2, kv_quant="log8", eos_id=-3)):
        assert H.run_trace(eng, trace) == slotted
        H.audit(eng)


def test_kv_quant_kernel_serving_never_gathers_dense_view(monkeypatch):
    """The acceptance criterion's 'no paged_dense_view on the hot paths':
    with NLDPE_PAGED_KERNEL=1 a quantized spec engine must serve a whole
    trace — chunk prefill, decode, draft decode, spec verify — without
    ever materializing the gathered dense view.  A fresh engine traces
    all its jits inside the poisoned scope, so ANY dense-view fallback on
    any hot path raises at trace time."""
    import repro.nn.attention as A
    from repro.launch.engine import PagedServeEngine

    def boom(cache):
        raise AssertionError("paged_dense_view materialized on a hot path")

    monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    monkeypatch.setattr(A, "paged_dense_view", boom)
    eng = PagedServeEngine(H.CFG, H.shared_params(), kv_quant="log8",
                           spec_k=2, spec_draft=H.WQ_DRAFT,
                           **H.engine_kwargs(page_size=H.PAGE,
                                             num_pages=H.NUM_PAGES))
    trace = [((3, 1, 4, 1, 5, 9, 2, 6), 5, 0), ((3, 1, 4, 2), 4, 1)]
    out = H.run_trace(eng, trace)
    assert all(len(t) > 0 for t in out.values())
    H.audit(eng)


# ---------------------------------------------------------------------------
# the telemetry on/off column (ISSUE 8): observation is never control flow.
# Dedicated tests (not extra ENGINES() rows) so the matrix's compile count
# stays put; CI's telemetry-interpret leg selects them with -k telemetry.
# ---------------------------------------------------------------------------

TELEMETRY_SPEC_K = 2 if 2 in SPEC_KS else SPEC_KS[0]


def test_telemetry_bit_identity_greedy():
    """The tentpole contract: engines with telemetry enabled emit exactly
    the tokens of the same engines with it disabled — slotted, paged
    (spec_k=0), and speculative — on seeded greedy Poisson traces."""
    for seed in (0, 2):
        trace = random_greedy_trace(np.random.default_rng(seed))
        for name, plain, instrumented in [
                ("slotted", H.slotted_engine(),
                 H.slotted_engine(telemetry=True)),
                ("paged", H.paged_engine(),
                 H.paged_engine(telemetry=True)),
                (f"spec{TELEMETRY_SPEC_K}",
                 H.paged_engine(spec_k=TELEMETRY_SPEC_K),
                 H.paged_engine(spec_k=TELEMETRY_SPEC_K, telemetry=True))]:
            assert H.run_trace(instrumented, trace) \
                == H.run_trace(plain, trace), \
                f"telemetry changed {name} tokens (seed {seed})"
            if hasattr(instrumented, "pool"):
                H.audit(instrumented)


def test_telemetry_bit_identity_sampled():
    """Same contract under mixed greedy/temperature/top-k sampling: the
    observed engines reproduce every sampled draw bit-for-bit."""
    for seed in (10, 12):
        trace = random_mixed_trace(np.random.default_rng(seed))
        assert H.run_trace(H.slotted_engine(telemetry=True), trace) \
            == H.run_trace(H.slotted_engine(), trace)
        spec_on = H.paged_engine(spec_k=TELEMETRY_SPEC_K, telemetry=True)
        assert H.run_trace(spec_on, trace) \
            == H.run_trace(H.paged_engine(spec_k=TELEMETRY_SPEC_K), trace)
        H.audit(spec_on)


def test_telemetry_bit_identity_cow_eviction():
    """On/off identity through the stressful pool paths — COW forks and
    zero-headroom LRU eviction — with the instrumented engine's eviction/
    cow_fork events actually firing."""
    trace = H.shared_prefix_cow_trace()
    on = H.paged_engine(spec_k=TELEMETRY_SPEC_K, telemetry=True)
    assert H.run_trace(on, trace) \
        == H.run_trace(H.paged_engine(spec_k=TELEMETRY_SPEC_K), trace)
    H.audit(on)
    kinds = {e["ev"] for e in on.telemetry.trace}
    assert "cow_fork" in kinds
    assert "eviction" in kinds


# ---------------------------------------------------------------------------
# hypothesis fuzz: extra depth when the optional dep is present
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    GREEDY_TRACES, MIXED_TRACES = H.make_strategies()

    @given(GREEDY_TRACES)
    @settings(max_examples=6, deadline=None)
    def test_hypothesis_greedy_traces_all_engines_agree(trace):
        check_greedy_trace(trace)

    @given(MIXED_TRACES)
    @settings(max_examples=6, deadline=None)
    def test_hypothesis_mixed_traces_contracts(trace):
        check_mixed_trace(trace)


# ---------------------------------------------------------------------------
# the hierarchical-cache column (ISSUE 9): host-RAM spill tier + priority
# preemption.  Oversubscribed device pools (smaller than the zero-headroom
# NUM_PAGES) force constant eviction into the host tier; every test name
# carries "spill" so CI's spill-interpret leg selects them with -k spill.
# ---------------------------------------------------------------------------

SPILL_POOL = 8          # device pages (vs NUM_PAGES = 12 zero-headroom)
HOST_PAGES = 6          # host-tier budget


def spill_engine(spec_k=0, **over):
    """The two-tier singleton: same reduced model, oversubscribed device
    pool backed by a host spill tier."""
    return H.paged_engine(spec_k=spec_k, num_pages=SPILL_POOL,
                          host_cache_pages=HOST_PAGES, **over)


def spill_restore_trace(seed=5):
    """Deterministic spill-then-restore trace: a 3-page shared prefix is
    published, evicted to host by two long fillers decoding concurrently,
    then hit twice more — the hits must restore host->device instead of
    re-prefilling."""
    rng = np.random.default_rng(seed)
    shared = tuple(int(x) for x in
                   rng.integers(0, H.CFG.vocab_size, 3 * H.PAGE))
    filler1 = tuple(int(x) for x in rng.integers(0, 64, 11))
    filler2 = tuple(int(x) for x in rng.integers(0, 64, 10))
    return [(shared, 3, 0), (filler1, 6, 9), (filler2, 6, 0),
            (shared + (1,), 4, 9), (shared, 2, 9)]


def priority_requests(base_tick, temps=(0.0, 0.0), lens=(9, 10, 8),
                      gens=(6, 6, 4)):
    """Two low-priority requests saturate both slots; a high-priority
    arrival one tick later can only be admitted by preemption.  ``lens``/
    ``gens`` let the speculative column shrink the page footprints so
    both low-priority requests actually co-reside (spec_k slack pages
    would otherwise leave a slot free — no preemption to test)."""
    rng = np.random.default_rng(11)
    prompts = [tuple(int(x) for x in rng.integers(0, 64, n)) for n in lens]
    return [Request(rid=0, tokens=prompts[0], max_new_tokens=gens[0],
                    temperature=temps[0], arrival=base_tick),
            Request(rid=1, tokens=prompts[1], max_new_tokens=gens[1],
                    temperature=temps[1], arrival=base_tick),
            Request(rid=2, tokens=prompts[2], max_new_tokens=gens[2],
                    arrival=base_tick + 1, priority=2)]


def test_spill_restore_bit_identity_greedy():
    """Tentpole acceptance: requests whose prefix pages were spilled to
    host and restored produce tokens bit-identical to the unlimited-pool
    engine — on the deterministic restore trace (spills AND restores must
    actually fire) and on oversubscribed Poisson traces."""
    eng = spill_engine()
    before = dict(eng.pool.stats)
    trace = spill_restore_trace()
    got = H.run_trace(eng, trace)
    H.audit(eng)
    st = dict(eng.pool.stats)
    assert st["spilled"] > before["spilled"], "trace never spilled"
    assert st["restored"] > before["restored"], "trace never restored"
    assert H.run_trace(H.paged_engine(), trace) == got
    for seed in (0, 2, 3):
        trace = random_greedy_trace(np.random.default_rng(seed))
        assert H.run_trace(eng, trace) \
            == H.run_trace(H.paged_engine(), trace), \
            f"spill engine diverged on greedy seed {seed}"
        H.audit(eng)


def test_spill_restore_bit_identity_sampled():
    """Same contract under mixed greedy/temperature/top-k traffic: the
    per-(slot-key, position) sampling fold makes every draw independent
    of physical page placement, so host round-trips must not perturb
    sampled tokens either."""
    eng = spill_engine()
    for seed in (10, 12):
        trace = random_mixed_trace(np.random.default_rng(seed))
        assert H.run_trace(eng, trace) \
            == H.run_trace(H.paged_engine(), trace), \
            f"spill engine diverged on mixed seed {seed}"
        H.audit(eng)


def test_spill_restore_speculative_column():
    """A speculative engine over the two-tier pool: restored pages feed
    the draft and verify passes, tokens stay bit-equal to the unlimited
    spec engine."""
    spec = spill_engine(spec_k=TELEMETRY_SPEC_K)
    before = dict(spec.pool.stats)
    trace = spill_restore_trace()
    got = H.run_trace(spec, trace)
    H.audit(spec)
    assert spec.pool.stats["spilled"] > before["spilled"]
    assert spec.spec_stats["drafted"] > 0
    assert H.run_trace(H.paged_engine(spec_k=TELEMETRY_SPEC_K), trace) == got


def test_spill_preemption_mixed_priority():
    """Priority preemption acceptance: a high-priority arrival preempts a
    saturated engine's lowest-priority slot (pages + decode state swapped
    to host); the victim resumes and every request — greedy and sampled —
    emits exactly the tokens of a never-preempted run."""
    for temps in ((0.0, 0.0), (0.9, 0.0)):
        eng = spill_engine()
        pre_before, res_before = eng.preempts, eng.resumes
        reqs = priority_requests(eng.tick, temps)
        got = {c.rid: c.tokens for c in eng.run(reqs)}
        assert eng.preempts > pre_before, "high priority never preempted"
        assert eng.resumes > res_before
        H.audit(eng)
        # the never-preempted twin: same requests, priorities stripped
        # (FIFO admission -> no preemption), on the unlimited pool
        base = H.paged_engine()
        plain = [Request(rid=r.rid, tokens=r.tokens,
                         max_new_tokens=r.max_new_tokens,
                         temperature=r.temperature, top_k=r.top_k,
                         seed=r.seed, arrival=base.tick + (r.arrival
                                                           - reqs[0].arrival))
                 for r in reqs]
        exp = {c.rid: c.tokens for c in base.run(plain)}
        assert got == exp, f"preemption changed tokens (temps={temps})"
        H.audit(base)
        for r in reqs:
            if r.temperature == 0.0:
                assert got[r.rid] == H.run_alone(r.tokens, r.max_new_tokens)


def test_spill_preemption_speculative_column():
    """Preempting a speculating slot: the drafted/accepted carry survives
    the host round-trip, greedy outputs still match the oracle."""
    spec = spill_engine(spec_k=TELEMETRY_SPEC_K)
    pre_before = spec.preempts
    reqs = priority_requests(spec.tick, lens=(7, 6, 6), gens=(6, 6, 3))
    got = {c.rid: c.tokens for c in spec.run(reqs)}
    assert spec.preempts > pre_before
    H.audit(spec)
    for r in reqs:
        assert got[r.rid] == H.run_alone(r.tokens, r.max_new_tokens)


def test_spill_telemetry_twin_stats_bit_identical():
    """Fresh telemetry-on/off twins of the two-tier engine over the same
    spill + preemption schedule: tokens, the full pool stats dict (both
    tiers), and the spec acceptance counters must be bit-identical —
    observation is never control flow — and the instrumented twin's trace
    must actually record the new spill/restore/preempt/resume events."""
    from repro.launch.engine import PagedServeEngine

    def fresh(telemetry):
        kw = H.engine_kwargs(page_size=H.PAGE, num_pages=SPILL_POOL,
                             host_cache_pages=HOST_PAGES,
                             spec_k=TELEMETRY_SPEC_K, spec_draft=H.WQ_DRAFT,
                             telemetry=telemetry)
        return PagedServeEngine(H.CFG, H.shared_params(), **kw)

    outs, engines = [], []
    for telemetry in (True, False):
        eng = fresh(telemetry)
        out = H.run_trace(eng, spill_restore_trace())
        out.update({100 + c.rid: c.tokens
                    for c in eng.run([Request(rid=r.rid + 100,
                                              tokens=r.tokens,
                                              max_new_tokens=r.max_new_tokens,
                                              temperature=r.temperature,
                                              priority=r.priority,
                                              arrival=r.arrival)
                                      for r in priority_requests(
                                          eng.tick, lens=(7, 6, 6),
                                          gens=(6, 6, 3))])})
        H.audit(eng)
        outs.append(out)
        engines.append(eng)
    on, off = engines
    assert outs[0] == outs[1], "telemetry changed two-tier tokens"
    assert dict(on.pool.stats) == dict(off.pool.stats)
    assert (on.preempts, on.resumes) == (off.preempts, off.resumes)
    assert on.spec_stats["drafted"] == off.spec_stats["drafted"]
    assert on.spec_stats["accepted"] == off.spec_stats["accepted"]
    assert on.pool.stats["spilled"] > 0 and on.pool.stats["restored"] > 0
    kinds = {e["ev"] for e in on.telemetry.trace}
    assert {"spill", "restore", "preempt", "resume"} <= kinds, kinds


# ---------------------------------------------------------------------------
# the async pipeline column (ISSUE 10): the dispatch/drain pipeline over
# AOT-bucketed prefill vs the plain tick-loop engines.  Each comparison
# covers BOTH tentpole halves at once — the async engines wrap bucketed
# twins, the sync side stays unbucketed — so a divergence in either the
# bucket executables or the pipeline's harvest ordering fails the column.
# Every test name carries "async" for CI's async-interpret leg (-k async).
# ---------------------------------------------------------------------------


def test_async_bit_identity_greedy():
    """Tentpole acceptance: the async pipeline emits exactly the tick
    loop's tokens — slotted and paged — on seeded greedy Poisson traces."""
    for seed in (0, 1, 2):
        trace = random_greedy_trace(np.random.default_rng(seed))
        for kind, sync in (("slotted", H.slotted_engine()),
                           ("paged", H.paged_engine())):
            a = H.async_engine(kind)
            assert H.run_trace(a, trace) == H.run_trace(sync, trace), \
                f"async {kind} diverged (seed {seed})"
            if kind == "paged":
                H.audit(a.engine)
    assert a.engine.aot_prefill, "paged async engine lost AOT buckets"


def test_async_bit_identity_sampled():
    """Mixed greedy/temperature/top-k traffic: the position-folded sampling
    makes every draw schedule-invariant, so pipelined dispatch must
    reproduce each sampled token bit-for-bit too."""
    for seed in (10, 11, 12):
        trace = random_mixed_trace(np.random.default_rng(seed))
        assert H.run_trace(H.async_engine("slotted"), trace) \
            == H.run_trace(H.slotted_engine(), trace)
        a = H.async_engine("paged")
        assert H.run_trace(a, trace) == H.run_trace(H.paged_engine(), trace)
        H.audit(a.engine)


def test_async_speculative_column():
    """Speculative ticks are host-synchronous inside the engine, so the
    async wrapper pipelines only admission-vs-decode around them — outputs
    must still match the sync spec engine exactly, shared-prefix COW trace
    included."""
    k = TELEMETRY_SPEC_K
    for trace in (random_greedy_trace(np.random.default_rng(3)),
                  H.shared_prefix_cow_trace()):
        a = H.async_engine("paged", spec_k=k)
        assert H.run_trace(a, trace) \
            == H.run_trace(H.paged_engine(spec_k=k), trace)
        H.audit(a.engine)
    assert a.engine.spec_stats["drafted"] > 0


def test_async_spill_preemption_column():
    """The two-tier column through the pipeline: spill/restore traffic and
    priority preemption — the flush-before-admission barrier must keep the
    scheduler from preempting (or re-tenanting) slots whose finishes sit
    un-harvested in the drain queue."""
    a = H.async_engine("paged", num_pages=SPILL_POOL,
                       host_cache_pages=HOST_PAGES)
    trace = spill_restore_trace()
    before = dict(a.engine.pool.stats)
    got = H.run_trace(a, trace)
    H.audit(a.engine)
    assert a.engine.pool.stats["spilled"] > before["spilled"]
    assert H.run_trace(spill_engine(), trace) == got
    pre_before = a.engine.preempts
    reqs = priority_requests(a.tick)
    got = {c.rid: c.tokens for c in a.run(reqs)}
    assert a.engine.preempts > pre_before, "high priority never preempted"
    H.audit(a.engine)
    sync = spill_engine()
    reqs = priority_requests(sync.tick)
    assert {c.rid: c.tokens for c in sync.run(reqs)} == got


def test_async_telemetry_twin():
    """An instrumented async engine reproduces the plain sync engine's
    tokens (observation is never control flow, threads included) and its
    trace carries the same lifecycle events the sync instrumented engine
    records — plus the pipeline's own admission/flush/drain spans."""
    trace = random_greedy_trace(np.random.default_rng(4))
    a = H.async_engine("paged", telemetry=True)
    a.telemetry.reset()
    got = H.run_trace(a, trace)
    assert got == H.run_trace(H.paged_engine(), trace)
    H.audit(a.engine)
    s = a.telemetry.summary()
    assert s["requests_finished"] == len(trace)
    assert s["ttft_s"]["count"] == len(trace)
    assert {"dispatch", "drain", "harvest", "decode_block", "admission",
            "flush", "admission_wave"} <= set(s["phases"])
    kinds = {e["ev"] for e in a.telemetry.trace}
    assert {"enqueue", "admit", "first_token", "finish",
            "admission_wave", "decode_block"} <= kinds


def test_async_sharded_column():
    """A mesh-backed engine through the pipeline (dp=tp=1 runs on one
    device in-process): sharded engines keep lazily-compiled bucket jits
    (aot_prefill=False — AOT input-sharding matching is brittle) but the
    padding semantics are identical, and tokens must match the unsharded
    sync engine bit-for-bit under serve_exact rules."""
    trace = random_greedy_trace(np.random.default_rng(5))
    a = H.async_engine("paged", mesh_shape=(1, 1))
    assert not a.engine.aot_prefill
    assert a.engine._bucket_sizes, "mesh engine lost its bucket table"
    assert H.run_trace(a, trace) == H.run_trace(H.paged_engine(), trace)
    H.audit(a.engine)


def test_async_bucketed_prefill_isolated_from_pipeline():
    """The bucket half alone: a SYNC engine with prefill_buckets=True must
    match the plain sync engine (isolates the AOT executables from any
    pipeline effect), exercise padding, and report its bucket table."""
    eng = H.paged_engine(prefill_buckets=True)
    assert eng.aot_prefill
    pad0 = eng.prefill_pad_chunks
    for seed in (0, 6):
        trace = random_greedy_trace(np.random.default_rng(seed))
        assert H.run_trace(eng, trace) \
            == H.run_trace(H.paged_engine(), trace)
        H.audit(eng)
    assert eng.prefill_pad_chunks >= pad0
    st = eng._engine_stats()
    assert st["prefill_buckets"] == len(eng._bucket_sizes) > 0
    assert st["prefill_pad_chunks"] == eng.prefill_pad_chunks
