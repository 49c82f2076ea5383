"""Paged serve engine: bit-exactness with the slotted engine + pool
mechanics under real traffic (ISSUE 3 acceptance).

The contract (DESIGN.md §7): ``PagedServeEngine`` reproduces the PR 2
slotted ``ServeEngine``'s tokens **bit-exactly** on any trace — prefix
hits, COW forks, and LRU eviction included — because attention runs on the
gathered dense view of the page pool, which reconstructs the slotted score
rows exactly, and shared pages hold bit-identical K/V (K/V at a position
depend only on the token prefix; the NL-DPE exp grid anchors to the fixed
cache length).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import NLDPEConfig
from repro.launch.engine import PagedServeEngine, Request, ServeEngine
from repro.models import lm
from repro.nn.module import param_dtype

CFG = get_config("qwen2_5_3b", reduced=True)
MAX_LEN = 32
FUSED = NLDPEConfig(enabled=True, fused_dual_compute=True)


@pytest.fixture(scope="module")
def params():
    with param_dtype(jnp.float32):
        return lm.init_params(jax.random.key(0), CFG)


def make_engines(params, *, nldpe=None, max_len=MAX_LEN, slots=3,
                 page_size=4, num_pages=None, chunk=4, block=2):
    kw = dict(max_slots=slots, max_len=max_len, prefill_chunk=chunk,
              decode_block=block)
    if nldpe is not None:
        kw["nldpe"] = nldpe
    slotted = ServeEngine(CFG, params, **kw)
    paged = PagedServeEngine(CFG, params, page_size=page_size,
                             num_pages=num_pages, **kw)
    return slotted, paged


def shared_prefix_trace(rng, n, *, shared_len=8, max_suffix=6, max_gen=6,
                        share_p=0.6, arrival_scale=2):
    shared = tuple(int(x) for x in rng.integers(0, CFG.vocab_size,
                                                shared_len))
    reqs, t = [], 0
    for i in range(n):
        t += int(rng.poisson(arrival_scale))
        suffix = tuple(int(x) for x in rng.integers(
            0, CFG.vocab_size, int(rng.integers(1, max_suffix + 1))))
        toks = shared + suffix if rng.random() < share_p else suffix
        reqs.append(Request(rid=i, tokens=toks,
                            max_new_tokens=int(rng.integers(1, max_gen + 1)),
                            arrival=t))
    return reqs


def run_both(slotted, paged, reqs):
    a = {c.rid: c.tokens for c in slotted.run(reqs)}
    b = {c.rid: c.tokens for c in paged.run(reqs)}
    paged.pool.check()
    return a, b


# ---------------------------------------------------------------------------
# the acceptance criterion: paged == slotted bit-exactly, OFF and fused
# ---------------------------------------------------------------------------

def test_mixed_shared_prefix_trace_bit_exact_off(params):
    slotted, paged = make_engines(params)
    rng = np.random.default_rng(7)
    reqs = shared_prefix_trace(rng, 10)
    a, b = run_both(slotted, paged, reqs)
    assert a == b
    st = paged.stats
    assert st["hits"] >= 1, "trace never exercised the radix index"
    assert st["prefill_tokens_saved"] > 0
    assert paged.free_slots == paged.max_slots


@pytest.mark.slow
def test_mixed_shared_prefix_trace_bit_exact_fused(params):
    """NL-DPE fused numerics: shared prefix pages hold the exact quantized
    K/V the request would have computed itself (exp grid anchors to the
    cache length, which chunked prefill fixes for both engines)."""
    slotted, paged = make_engines(params, nldpe=FUSED, max_len=24, slots=2)
    rng = np.random.default_rng(5)
    reqs = shared_prefix_trace(rng, 4, shared_len=4, max_suffix=3, max_gen=3,
                               arrival_scale=1)
    a, b = run_both(slotted, paged, reqs)
    assert a == b
    assert paged.stats["hits"] >= 1


def test_cow_fork_on_fully_cached_prompt(params):
    """A prompt exactly covered by cached pages forks its boundary page:
    the final token recomputes into the private copy (its logits seed
    sampling) and decode appends there, leaving the shared page intact for
    the next hit."""
    slotted, paged = make_engines(params, slots=2)
    rng = np.random.default_rng(3)
    prompt = tuple(int(x) for x in rng.integers(0, CFG.vocab_size, 8))
    reqs = [Request(rid=0, tokens=prompt, max_new_tokens=5, arrival=0),
            Request(rid=1, tokens=prompt, max_new_tokens=5, arrival=50),
            Request(rid=2, tokens=prompt, max_new_tokens=3, arrival=100),
            Request(rid=3, tokens=prompt + (3, 1), max_new_tokens=4,
                    arrival=150)]
    a, b = run_both(slotted, paged, reqs)
    assert a == b
    assert paged.stats["cow_forks"] >= 2         # rids 1 and 2 fork
    # identical greedy requests reproduce each other exactly (the forked
    # page's recomputed final token bit-matches the shared original's)
    assert b[1] == b[0] and b[2] == b[0][:len(b[2])]


@pytest.mark.parametrize("page_size,chunk", [(4, 16), (3, 8), (5, 16)])
def test_page_size_chunk_misalignment_bit_exact(params, page_size, chunk):
    """page_size != prefill_chunk: a chunk's padded tail positions reach
    past a short slot's allocated blocks.  Those writes must DROP through
    the out-of-range block-table sentinel — routing them through a default
    entry of 0 would corrupt physical page 0 under another slot or the
    radix cache (regression test: found by review, every aligned
    page_size == prefill_chunk config masks it)."""
    slotted, paged = make_engines(params, page_size=page_size, chunk=chunk)
    rng = np.random.default_rng(41)
    reqs = [Request(rid=0, tokens=tuple(int(x) for x in
                                        rng.integers(0, 256, 9)),
                    max_new_tokens=12, arrival=0),
            Request(rid=1, tokens=tuple(int(x) for x in
                                        rng.integers(0, 256, 5)),
                    max_new_tokens=2, arrival=3)]       # admits mid-decode
    a, b = run_both(slotted, paged, reqs)
    assert a == b


def test_eviction_trace_bit_exact(params):
    """A pool with zero headroom (slots * blocks pages) must evict cached
    pages between waves and still reproduce slotted tokens."""
    slotted, paged = make_engines(params, max_len=16, slots=2, num_pages=8)
    rng = np.random.default_rng(11)
    reqs = []
    t = 0
    for i in range(12):
        t += int(rng.poisson(3))
        plen = int(rng.integers(2, 12))
        reqs.append(Request(
            rid=i,
            tokens=tuple(int(x) for x in rng.integers(0, CFG.vocab_size,
                                                      plen)),
            max_new_tokens=int(rng.integers(1, 5)), arrival=t))
    a, b = run_both(slotted, paged, reqs)
    assert a == b
    assert paged.stats["evicted"] >= 1


def test_oversubscribed_pool_waits_for_pages(params):
    """num_pages below slots * blocks: slots outnumber the physical cache,
    so admission stalls on pages instead of slots — the capacity decoupling
    the paged pool exists for — and outputs still match the slotted engine
    (which needs the full slots * max_len reservation to serve the same
    trace)."""
    slotted, paged = make_engines(params, max_len=16, slots=3,
                                  num_pages=7, page_size=4)
    rng = np.random.default_rng(13)
    reqs = [Request(rid=i,
                    tokens=tuple(int(x) for x in rng.integers(
                        0, CFG.vocab_size, int(rng.integers(4, 12)))),
                    max_new_tokens=int(rng.integers(2, 5)), arrival=0)
            for i in range(6)]
    a, b = run_both(slotted, paged, reqs)
    assert a == b
    assert paged.free_slots == paged.max_slots
    assert paged.pool.available() == paged.pool.num_pages


def test_quantized_kv_cache_paged_matches_slotted(params):
    """int8 KV cache: page pools carry the quantized codes + scales and the
    gathered view reproduces the slotted quantized cache bit-for-bit."""
    qcfg = dataclasses.replace(CFG, kv_cache_dtype="int8")
    with param_dtype(jnp.float32):
        qparams = lm.init_params(jax.random.key(2), qcfg)
    slotted = ServeEngine(qcfg, qparams, max_slots=2, max_len=16,
                          prefill_chunk=4, decode_block=2)
    paged = PagedServeEngine(qcfg, qparams, max_slots=2, max_len=16,
                             prefill_chunk=4, decode_block=2, page_size=4)
    rng = np.random.default_rng(17)
    reqs = shared_prefix_trace(rng, 5, shared_len=4, max_suffix=4, max_gen=4)
    a = {c.rid: c.tokens for c in slotted.run(reqs)}
    b = {c.rid: c.tokens for c in paged.run(reqs)}
    assert a == b


def test_kv_quant_ctor_param_selects_log_grid(params):
    """PagedServeEngine(kv_quant="log8") is exactly serving with
    kv_cache_dtype="log8": the engines rewrite their config, carry the
    effective mode on .kv_quant, and paged still matches slotted
    bit-for-bit over radix hits and COW forks."""
    slotted = ServeEngine(CFG, params, max_slots=2, max_len=16,
                          prefill_chunk=4, decode_block=2, kv_quant="log8")
    paged = PagedServeEngine(CFG, params, max_slots=2, max_len=16,
                             prefill_chunk=4, decode_block=2, page_size=4,
                             kv_quant="log8")
    for eng in (slotted, paged):
        assert eng.kv_quant == "log8"
        assert eng.cfg.kv_cache_dtype == "log8"
    assert "k_scale" in paged.cache["groups"]["b0"]["attn"]
    rng = np.random.default_rng(17)
    reqs = shared_prefix_trace(rng, 5, shared_len=4, max_suffix=4, max_gen=4)
    a = {c.rid: c.tokens for c in slotted.run(reqs)}
    b = {c.rid: c.tokens for c in paged.run(reqs)}
    assert a == b
    with pytest.raises(ValueError, match="kv_quant"):
        ServeEngine(CFG, params, max_slots=1, max_len=8, kv_quant="fp4")


# ---------------------------------------------------------------------------
# pool/scheduler mechanics
# ---------------------------------------------------------------------------

def test_windowed_arch_rejected(params):
    wcfg = dataclasses.replace(CFG, layer_pattern=("local", "attn"), window=6)
    with pytest.raises(NotImplementedError, match="non-windowed"):
        PagedServeEngine(wcfg, params, max_slots=1, max_len=8)


def test_impossible_request_raises_instead_of_spinning(params):
    """A request whose footprint exceeds the whole pool can never admit;
    run() must raise, not live-lock waiting for pages."""
    paged = PagedServeEngine(CFG, params, max_slots=2, max_len=MAX_LEN,
                             prefill_chunk=4, decode_block=2, page_size=4,
                             num_pages=3)                  # 12 positions max
    with pytest.raises(RuntimeError, match="pages"):
        paged.run([Request(rid=0, tokens=tuple(range(14)),
                           max_new_tokens=4)])


def test_submit_on_exhausted_pool_raises_and_rolls_back(params):
    paged = PagedServeEngine(CFG, params, max_slots=2, max_len=16,
                             prefill_chunk=4, decode_block=2, page_size=4,
                             num_pages=4)
    first = Request(rid=0, tokens=(1, 2, 3, 4, 5), max_new_tokens=8)
    assert paged.submit(first) is None          # holds 3 of the 4 pages
    with pytest.raises(RuntimeError, match="exhausted"):
        paged.submit(Request(rid=1, tokens=(6, 7, 8, 9), max_new_tokens=8))
    assert paged.free_slots == 1                # rejected slot returned
    while paged.any_active:                     # first request unharmed
        paged.step()
    paged.pool.check()
    assert paged.pool.available() == paged.pool.num_pages


def test_failed_wave_leaves_stats_and_cache_untouched(params):
    """Regression: when a later wave member's alloc fails, the rollback
    must also leave the pool's prefix-savings stats and the device cache
    exactly as before the wave — COW copies and stat bumps for committed
    members only happen once every allocation in the wave succeeded."""
    paged = PagedServeEngine(CFG, params, max_slots=3, max_len=MAX_LEN,
                             prefill_chunk=4, decode_block=2, page_size=4,
                             num_pages=5)
    prompt = tuple(range(8))                    # exactly 2 full pages
    done = paged.run([Request(rid=0, tokens=prompt, max_new_tokens=1)])
    assert len(done) == 1                       # both prompt pages cached
    stats_before = dict(paged.pool.stats)
    avail_before = paged.pool.available()
    cache_before = paged.cache
    hit = Request(rid=1, tokens=prompt, max_new_tokens=1)      # COW fork
    big = Request(rid=2, tokens=tuple(range(10, 22)),          # 5 fresh
                  max_new_tokens=8)
    with pytest.raises(RuntimeError, match="exhausted"):
        paged._admit_wave([hit, big])
    assert paged.cache is cache_before          # no COW copy dispatched
    for k in ("cow_forks", "prefill_tokens_saved", "published", "evicted"):
        assert paged.pool.stats[k] == stats_before[k]
    assert paged.free_slots == paged.max_slots
    assert paged.pool.available() == avail_before
    paged.pool.check()
    # the wave members admit fine one at a time afterwards
    assert paged.submit(hit) is not None        # max_new_tokens=1: instant
    paged.pool.check()


def test_prefix_hits_share_physical_pages(params):
    """Two live requests with the same system prompt must map the same
    physical pages (refcount 2), not copies."""
    paged = PagedServeEngine(CFG, params, max_slots=2, max_len=MAX_LEN,
                             prefill_chunk=4, decode_block=2, page_size=4)
    shared = tuple(range(8))
    paged.submit(Request(rid=0, tokens=shared + (30,), max_new_tokens=12))
    paged.submit(Request(rid=1, tokens=shared + (31,), max_new_tokens=12))
    shared_pages = set(paged._slot_pages[0]) & set(paged._slot_pages[1])
    assert len(shared_pages) == 2               # both full prompt pages
    assert all(paged.pool.refcount(p) == 2 for p in shared_pages)
    while paged.any_active:
        paged.step()
    paged.pool.check()


def test_paged_kernel_decode_opt_in(params, monkeypatch):
    """NLDPE_PAGED_KERNEL=1 routes OFF-mode paged decode through the
    Pallas paged-attention kernel (interpret mode on CPU) instead of the
    gathered dense view.  The kernel matches the lax twin within float
    tolerance, not bitwise — but greedy argmax over well-separated logits
    must still emit the same tokens as the slotted oracle."""
    monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    slotted, paged = make_engines(params, slots=2)
    rng = np.random.default_rng(29)
    reqs = shared_prefix_trace(rng, 4, max_gen=4)
    a, b = run_both(slotted, paged, reqs)
    assert a == b


def test_stats_expose_prefix_metrics(params):
    _, paged = make_engines(params)
    rng = np.random.default_rng(23)
    paged.run(shared_prefix_trace(rng, 6))
    st = paged.stats
    for key in ("lookups", "hits", "prefill_tokens_saved", "evicted",
                "cow_forks", "published"):
        assert key in st
    assert st["lookups"] == 6


def _paged_attention_jaxprs(spec):
    """The jaxprs of one paged decode and one paged chunk ``attn_apply``."""
    from repro.nn import attention
    p = attention.attn_init(jax.random.key(1), spec)
    cache = attention.init_paged_cache(spec, 2, 16, num_pages=8,
                                       page_size=4, dtype=jnp.float32)
    x1 = jnp.zeros((2, 1, spec.d_model), jnp.float32)
    x4 = jnp.zeros((2, 4, spec.d_model), jnp.float32)
    pos = jnp.asarray([[3], [5]], jnp.int32)
    chunk = pos + jnp.arange(4, dtype=jnp.int32)
    return [str(jax.make_jaxpr(lambda c, x, q: attention.attn_apply(
        p, spec, x, positions=q, mode=mode, cache=c))(cache, x, q))
        for mode, x, q in (("decode", x1, pos), ("chunk", x4, chunk))]


def test_paged_attention_path_follows_the_platform(monkeypatch):
    """The platform chooses the paged attention path: on the CPU decode
    and chunk attention gather the dense view (no kernel in the program);
    with the backend seen as a TPU they read pages through the Pallas
    kernel — unless NL-DPE numerics, a softcap or a window keep the view.
    NLDPE_PAGED_KERNEL=1 forces the kernel where the platform would not."""
    from repro.nn import attention
    spec = lm._attn_spec(CFG, "attn")
    monkeypatch.delenv("NLDPE_PAGED_KERNEL", raising=False)
    assert not attention.paged_kernel_path(NLDPEConfig(enabled=False), spec)
    for text in _paged_attention_jaxprs(spec):
        assert "pallas_call" not in text and "gather" in text
    monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    assert attention.paged_kernel_path(NLDPEConfig(enabled=False), spec)
    monkeypatch.delenv("NLDPE_PAGED_KERNEL")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attention.paged_kernel_path(NLDPEConfig(enabled=False), spec)
    for text in _paged_attention_jaxprs(spec):
        assert "pallas_call" in text
    assert not attention.paged_kernel_path(FUSED, spec)
    for gate in (dict(softcap=30.0), dict(window=8)):
        assert not attention.paged_kernel_path(
            NLDPEConfig(enabled=False), dataclasses.replace(spec, **gate))


@pytest.mark.parametrize("kernel", [False, True], ids=["view", "kernel"])
def test_decode_ticks_count_the_pages_attention_reads(params, monkeypatch,
                                                      kernel):
    """Each tick's ``decode_block`` span carries the pages the kernel
    reads in the tick's last step — Σ over owned slots of ceil((pos +
    decode_block) / page_size), ``pos`` from the prompt and the tokens
    harvested before the tick — beside the dense view's slots × blocks;
    both None where attention gathers the view (the CPU default)."""
    if kernel:
        monkeypatch.setenv("NLDPE_PAGED_KERNEL", "1")
    else:
        monkeypatch.delenv("NLDPE_PAGED_KERNEL", raising=False)
    slots, ps, block = 2, 4, 2
    paged = PagedServeEngine(CFG, params, max_slots=slots, max_len=MAX_LEN,
                             prefill_chunk=4, decode_block=block,
                             page_size=ps, telemetry=True)
    reqs = [Request(rid=0, tokens=tuple(range(1, 6)), max_new_tokens=7),
            Request(rid=1, tokens=tuple(range(1, 10)), max_new_tokens=4)]
    plen = {r.rid: len(r.tokens) for r in reqs}
    paged.run(reqs)
    events = list(paged.telemetry.trace)
    ticks = {e["span"]: e for e in events if e["ev"] == "decode_block"}
    assert ticks
    if not kernel:
        assert all(e["kv_blocks_read"] is None and e["kv_blocks_view"] is None
                   for e in ticks.values())
        return
    emitted = {rid: 1 for rid in plen}          # the wave's first tokens
    for h in (e for e in events if e["ev"] == "harvest"):
        tick = ticks[h["parent"]]
        want = sum(-(-min(plen[r] + emitted[r] - 1 + block, MAX_LEN) // ps)
                   for r in h["rids"])
        assert tick["kv_blocks_read"] == want, (tick, h)
        assert tick["kv_blocks_view"] == slots * (MAX_LEN // ps)
        for r, n in zip(h["rids"], h["n_emitted"]):
            emitted[r] += n
