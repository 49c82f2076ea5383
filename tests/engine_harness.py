"""Shared trace machinery for every serve-engine equivalence suite.

One place owns the reduced model, the lazily-built engines (jit compiles
amortized across hypothesis examples — the PR 2/PR 3 property files each
used to carry a private copy of this), the run-alone lockstep oracle, the
seeded np.random trace generators (the always-run mirrors of the
hypothesis strategies — hypothesis is an optional dev dep), and the
hypothesis strategies for random Poisson traces: tiny token alphabet
(dense prefix collisions -> radix hits, COW forks), mixed
greedy/temperature/top-k sampling, staggered arrivals, zero-headroom page
pools (constant LRU eviction pressure).

Engines take a ``mesh_shape`` axis: ``(dp, tp)`` builds a
``("data", "model")`` mesh over the first ``dp * tp`` host devices and
serves sharded (slots over "data", heads over "model" — ISSUE 5).  The
process must expose enough devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``, set by the
subprocess drivers in tests/test_engine_sharded.py).

Any extra engine kwarg flows through ``engine_kwargs`` into the singleton
key, so ``slotted_engine(telemetry=True)`` / ``paged_engine(spec_k=k,
telemetry=True)`` give the observability on/off column (ISSUE 8): the
instrumented twins must reproduce the plain engines' tokens bit-for-bit
(tests/test_engine_differential.py ``-k telemetry``).

tests/test_engine_differential.py drives the full engine matrix through
it; tests/test_engine_properties.py, tests/test_paged_engine_properties.py
and tests/sharded_driver.py keep only their distinctive assertions on top.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.engine import NLDPEConfig
from repro.launch.async_engine import AsyncServeEngine
from repro.launch.engine import PagedServeEngine, Request, ServeEngine
from repro.launch.mesh import serve_mesh
from repro.launch.serve import build_decode_step, python_loop_decode
from repro.models import lm
from repro.nn.module import param_dtype

CFG = get_config("qwen2_5_3b", reduced=True)
MAX_LEN = 24
PAGE = 4
SLOTS = 2
# zero-headroom pool: slots * ceil(max_len / page) pages, so radix-cached
# prompts are evicted as soon as live requests need their pages
NUM_PAGES = SLOTS * (-(-MAX_LEN // PAGE))
# weight-quant-only drafter for the fast suites: the conductance-programmed
# weights without the (simulation-expensive) analog activation numerics —
# greedy spec exactness holds for ANY drafter, so tests keep the cheap one
# and a dedicated slow test exercises the full analog path
WQ_DRAFT = NLDPEConfig(enabled=False)

_STATE = {}


def shared_params():
    if "params" not in _STATE:
        with param_dtype(jnp.float32):
            _STATE["params"] = lm.init_params(jax.random.key(0), CFG)
    return _STATE["params"]


def engine_kwargs(**over):
    kw = dict(max_slots=SLOTS, max_len=MAX_LEN, prefill_chunk=4,
              decode_block=2)
    kw.update(over)
    return kw


def mesh_for(mesh_shape):
    """(dp, tp) -> the serving mesh over the first dp*tp devices (cached;
    raises with the fake-device hint when the process is short — the
    sharded suites run in subprocesses that force 8)."""
    if mesh_shape is None:
        return None
    key = ("mesh", tuple(mesh_shape))
    if key not in _STATE:
        _STATE[key] = serve_mesh(*mesh_shape)
    return _STATE[key]


def _slotted(mesh_shape, **over) -> ServeEngine:
    return ServeEngine(CFG, shared_params(), **engine_kwargs(**over),
                       mesh=mesh_for(mesh_shape))


def _paged(spec_k, mesh_shape, **over) -> PagedServeEngine:
    kw = engine_kwargs(**{"page_size": PAGE, "num_pages": NUM_PAGES, **over})
    if spec_k:
        kw.update(spec_k=spec_k, spec_draft=WQ_DRAFT)
    return PagedServeEngine(CFG, shared_params(), **kw,
                            mesh=mesh_for(mesh_shape))


def slotted_engine(mesh_shape=None, **over) -> ServeEngine:
    key = ("slotted", None if mesh_shape is None else tuple(mesh_shape),
           tuple(sorted(over.items())))
    if key not in _STATE:
        _STATE[key] = _slotted(mesh_shape, **over)
    return _STATE[key]


def paged_engine(spec_k: int = 0, mesh_shape=None, **over) -> PagedServeEngine:
    """Module-level singletons per (spec_k, mesh_shape) (compile cache);
    the carried radix index must be invisible in outputs — carried cache
    can only turn misses into hits, never change tokens."""
    key = ("paged", spec_k, None if mesh_shape is None else tuple(mesh_shape),
           tuple(sorted(over.items())))
    if key not in _STATE:
        _STATE[key] = _paged(spec_k, mesh_shape, **over)
    return _STATE[key]


def async_engine(kind: str = "slotted", spec_k: int = 0, mesh_shape=None,
                 *, drain_depth: int = 4, **over) -> AsyncServeEngine:
    """Singleton async pipeline over an AOT-bucketed twin of the sync
    engine singletons.  The twin is the pipeline's own engine,
    not the sync singleton with the same kwargs: the pipeline's scheduler
    thread stays alive between traces and would race a test driving that
    engine directly.  The wrapper reuses its engine's compile cache
    across traces exactly like the sync singletons; ``run_trace`` works
    unchanged because the wrapper
    delegates ``.tick`` and keeps ``run()`` as a compat shim.  The
    differential column compares this against the PLAIN (unbucketed,
    tick-loop) singletons, so one comparison covers both tentpole halves:
    bucketed AOT prefill and the async dispatch/drain pipeline."""
    key = ("async", kind, spec_k,
           None if mesh_shape is None else tuple(mesh_shape),
           drain_depth, tuple(sorted(over.items())))
    if key not in _STATE:
        over = dict(over, prefill_buckets=True)
        eng = (_slotted(mesh_shape, **over) if kind == "slotted"
               else _paged(spec_k, mesh_shape, **over))
        _STATE[key] = AsyncServeEngine(eng, drain_depth=drain_depth)
    return _STATE[key]


def drift_engine(spec_k: int = 2, *, nu=0.5, t0=2.0, fault_rate=0.0,
                 dt_step=5.0, reprogram_s=0.0, seed=3, fidelity=None,
                 **over) -> PagedServeEngine:
    """A FRESH drift-injected spec engine (not a singleton: the aging
    device state and the monitor's ladder position are the test subject,
    so suites must not share them).  Defaults give fast, visible
    degradation on the reduced model; jit compilations still share the
    in-process jax cache with the singleton engines."""
    from repro.core.drift import DriftModel
    from repro.launch.fidelity import DriftInjection
    inj = DriftInjection(model=DriftModel(nu=nu, t0=t0,
                                          fault_rate=fault_rate),
                         seed=seed, dt_step=dt_step, reprogram_s=reprogram_s)
    kw = engine_kwargs(page_size=PAGE, num_pages=NUM_PAGES,
                       spec_k=spec_k, spec_draft=WQ_DRAFT,
                       drift=inj, fidelity=fidelity, **over)
    return PagedServeEngine(CFG, shared_params(), **kw)


def run_alone(prompt: tuple, gen_len: int) -> list:
    """The seed lockstep oracle: whole-prompt prefill + python_loop_decode,
    greedy, one request alone.  Cached per (prompt, gen)."""
    if "decode" not in _STATE:
        _STATE["decode"] = jax.jit(build_decode_step(CFG))
        _STATE["alone"] = {}
    key = (tuple(prompt), gen_len)
    if key not in _STATE["alone"]:
        cache = lm.init_model_cache(CFG, 1, MAX_LEN, dtype=jnp.float32)
        logits, cache = lm.forward(shared_params(),
                                   jnp.asarray([prompt], jnp.int32), CFG,
                                   mode="prefill", cache=cache)
        tok0 = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)
        gen, _ = python_loop_decode(_STATE["decode"], shared_params(), cache,
                                    tok0, len(prompt), gen_len)
        _STATE["alone"][key] = [int(t) for t in np.asarray(gen)[0]]
    return _STATE["alone"][key]


def to_requests(trace, base_tick: int = 0) -> list:
    """trace: list of (prompt, gen, gap[, temperature, top_k]) tuples."""
    reqs, t = [], 0
    for i, spec in enumerate(trace):
        prompt, gen, gap = spec[:3]
        temp, topk = (spec[3], spec[4]) if len(spec) > 3 else (0.0, 0)
        t += gap
        reqs.append(Request(rid=i, tokens=tuple(prompt), max_new_tokens=gen,
                            temperature=temp, top_k=topk,
                            arrival=base_tick + t))
    return reqs


def run_trace(engine, trace) -> dict:
    comps = engine.run(to_requests(trace, engine.tick))
    assert sorted(c.rid for c in comps) == list(range(len(trace)))
    return {c.rid: c.tokens for c in comps}


def audit(paged: PagedServeEngine) -> None:
    """Post-trace pool invariants: every slot free, allocator consistent,
    every page reclaimable (no leaks — speculative rejections included).
    pool.check() audits BOTH tiers: device refcounts and the host spill
    set (payloads present, pins cleared, host_used within budget)."""
    assert paged.free_slots == paged.max_slots
    assert not paged._preempted, "preempted requests left unresumed"
    paged.pool.check()
    assert paged.pool.available() == paged.pool.num_pages, \
        "page leak: rejected speculative pages must return to the pool"


# ---------------------------------------------------------------------------
# seeded np.random trace generators — the always-run mirrors of the
# hypothesis strategies below (hypothesis is an optional dev dep: on hosts
# without it, importorskip'd suites silently skip, so every
# acceptance-critical property must also run from these)
# ---------------------------------------------------------------------------

def random_greedy_trace(rng):
    """Tiny-alphabet Poisson trace: greedy requests only."""
    n = int(rng.integers(1, 6))
    return [(tuple(int(x) for x in rng.integers(0, 3,
                                                int(rng.integers(1, 11)))),
             int(rng.integers(1, 7)), int(rng.integers(0, 9)))
            for _ in range(n)]


def random_mixed_trace(rng):
    """Mixed sampling: greedy, temperature, temperature+top-k (top_k
    includes 0 = disabled and >= vocab_size = explicitly disabled)."""
    temps = [0.0, 0.0, 0.7, 1.3]
    topks = [0, 1, 3, CFG.vocab_size + 7]
    n = int(rng.integers(1, 6))
    return [(tuple(int(x) for x in rng.integers(0, 3,
                                                int(rng.integers(1, 11)))),
             int(rng.integers(1, 6)), int(rng.integers(0, 7)),
             temps[int(rng.integers(0, 4))], topks[int(rng.integers(0, 4))])
            for _ in range(n)]


def shared_prefix_cow_trace(seed: int = 17):
    """Deterministic acceptance trace: repeated identical prompts (COW
    forks), page-multiple prompt lengths, and enough distinct long prompts
    to force eviction in the zero-headroom pool."""
    rng = np.random.default_rng(seed)
    shared = tuple(int(x) for x in rng.integers(0, CFG.vocab_size, 2 * PAGE))
    return [(shared, 4, 0),                        # publishes both pages
            (shared, 4, 3),                        # full-prompt hit -> COW
            (shared + (1, 2), 3, 2),               # prefix hit + suffix
            (tuple(int(x) for x in rng.integers(0, 64, 11)), 5, 1),
            (shared, 2, 1),                        # hit after eviction churn
            (tuple(int(x) for x in rng.integers(0, 64, 9)), 4, 0)]


def make_strategies():
    """Hypothesis strategies (imported lazily so collection degrades to a
    skip when hypothesis is absent, mirroring the property files)."""
    from hypothesis import strategies as st

    # tiny alphabet + short lengths -> dense prefix collisions; lengths at
    # exact page multiples force the COW fork path
    greedy_request = st.tuples(
        st.lists(st.integers(0, 2), min_size=1, max_size=10),  # prompt
        st.integers(1, 6),          # max_new_tokens
        st.integers(0, 8),          # arrival gap to previous request
    )
    # mixed sampling: greedy, temperature, temperature+top-k — top_k
    # includes 0 (disabled) and a value >= vocab_size (explicitly disabled)
    mixed_request = st.tuples(
        st.lists(st.integers(0, 2), min_size=1, max_size=10),
        st.integers(1, 5),
        st.integers(0, 6),
        st.sampled_from([0.0, 0.0, 0.7, 1.3]),
        st.sampled_from([0, 1, 3, CFG.vocab_size + 7]),
    )
    return (st.lists(greedy_request, min_size=1, max_size=5),
            st.lists(mixed_request, min_size=1, max_size=5))
