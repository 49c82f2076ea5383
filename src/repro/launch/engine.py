"""Continuous-batching serve engine: slot-based KV cache + scheduler.

The lockstep server in ``launch/serve.py`` generates one fixed-shape batch:
every request prefills together, decodes together, and finishes together.
This module replaces that with a *server* (DESIGN.md §5):

* **Slots** — the KV cache is one slotted buffer of ``max_slots`` rows
  (``lm.init_model_cache(..., slotted=True)``), each row an independent
  sequence with its own position track.  Admission, decoding, and eviction
  never change any array shape, so nothing recompiles as traffic churns.
* **Admission / chunked prefill** — requests admitted in the same wave
  prefill *together, in place*: their slots' position tracks are reset,
  then fixed-size ``(max_slots, prefill_chunk)`` chunk calls run
  ``mode="chunk"`` attention over the shared cache with per-slot write
  masks (each chunk's queries attend to the whole per-slot cache under
  validity masking, so any chunk offset is correct), and finally padded
  tail positions are trimmed back to never-valid.  Chunking bounds both
  compile count (one shape) and per-admission latency; batching the wave
  keeps admission cost closer to one batched prefill than N sequential
  ones.
* **Decode** — one jit'd ``lax.scan`` of ``decode_block`` steps runs over
  *all* slots each tick; per-slot ``write_mask`` freezes finished/empty
  slots bit-for-bit, and per-slot positions keep staggered sequences
  independent.  Cross-slot leakage is structurally impossible: every slot
  reads and writes only its own cache row.
* **Sampling** — per-slot greedy / temperature / top-k
  (``launch/sampling.py``); sampled randomness depends only on
  (request seed, position), so outputs are independent of slot placement
  and co-tenants.
* **Eviction** — finishing a slot just marks it free; the next admission
  resets the row's position track, so no cleanup pass is needed.

Both engines serve **mesh-sharded** when given ``mesh=`` (DESIGN.md §9):
params/caches/state are placed per a logical-axis rule table (``rules=``,
default ``serve_exact``) and the per-tick jits trace under the sharding
context — heads shard over "model", slots over "data", the paged-attention
kernel dispatches per-shard via shard_map, and host-side scheduling stays
global.  Under the default rules, sharded outputs are bit-identical to
``mesh=None`` (tests/test_engine_sharded.py).

**Async serving** (DESIGN.md §14): both engines expose their decode tick
as ``_dispatch_tick()`` — device dispatch only, returning the emitted-token
device buffer plus a freshly allocated active-mask snapshot and the tick's
open telemetry span — and ``_collect()``, the host-side harvest, so
``launch/async_engine.AsyncServeEngine`` can run the two on separate
threads (device never blocks on detokenize-side work).
With ``prefill_buckets`` set, admission-wave chunk prefill additionally
runs as ONE per-bucket executable AOT-compiled at construction
(``jax.jit(...).lower(...).compile()``), waves padded to the bucket edge
with all-False write masks — bucket choice cannot change cache bytes.

``PagedServeEngine`` below replaces the per-slot worst-case cache rows
with a paged pool + radix prefix sharing (DESIGN.md §7): same scheduler,
same contracts, bit-exact outputs, but physical capacity decouples from
``max_slots * max_len`` and shared system prompts prefill once.  With
``spec_k > 0`` it additionally runs analog-draft speculative decoding
(DESIGN.md §8, ``launch/spec_decode.py``): the NL-DPE low-precision path
drafts ``spec_k`` tokens per slot and one exact batched chunk verifies
them — greedy outputs provably unchanged, 1..spec_k+1 tokens per verify.

Determinism contract (asserted in tests/test_serve_engine.py and
tests/test_engine_properties.py): a request served under any traffic mix
yields exactly the tokens of the same request served alone.  In OFF
numerics this also matches the legacy lockstep path (whole-prompt prefill +
``python_loop_decode``) exactly; in NL-DPE modes the *decode* numerics are
identical but whole-prompt prefill anchors its log-sum ACAM grid to the
prompt length while chunked prefill anchors to the cache length, so
prefill logits differ within quantization LSBs between the two prefill
styles (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from ..core import drift as drift_lib
from ..core.engine import NLDPEConfig, OFF
from ..models import lm
from ..models.lm import ATTN_TYPES
from ..obs import MetricsRegistry, Telemetry, span
from ..parallel import sharding
from ..parallel.context import sharding_ctx
from .fidelity import DriftInjection, FidelityMonitor, FidelityPolicy
from .kvpool import PagePool, nldpe_fingerprint
from .sampling import TOP_K_CAP, request_key, sample_tokens, step_keys
from .spec_decode import (batch_dim as _batch_dim, build_draft_scan_fn,
                          build_verify_fn, clip_positions, emits_tick_major,
                          per_slot as _per_slot, quantize_draft_params)


def _merge_last(last, lg, take, col):
    """Running (S, V) last-logits merge: each chunk contributes only the
    rows of slots whose last real prompt token lives in it, so wave memory
    never scales with chunk count (full (S, C, V) logits would be
    ~n_chunks x slots x chunk x vocab on a real vocabulary)."""
    rows = lg[jnp.arange(lg.shape[0]), col]                # (S, V)
    return jnp.where(take[:, None], rows, last)


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request entering the scheduler."""

    rid: int
    tokens: tuple[int, ...]            # prompt token ids, length >= 1
    max_new_tokens: int = 16           # total generated tokens (incl. first)
    temperature: float = 0.0           # 0 -> greedy; finite, >= 0
    top_k: int = 0                     # 0 -> disabled
    seed: int | None = None            # defaults to rid
    arrival: int = 0                   # arrival time in decode ticks
    priority: int = 0                  # higher admits first; strictly
    #                                    higher may preempt (paged engine)


@dataclasses.dataclass
class Completion:
    """Scheduler output for one finished request."""

    rid: int
    prompt: tuple[int, ...]
    tokens: list[int]                  # generated tokens, length <= max_new
    finish_reason: str                 # "length" | "eos"
    admitted_tick: int
    finished_tick: int


@dataclasses.dataclass
class _Preempted:
    """A preempted request's complete host-side resume image: its decode
    state row, sampling key, one canonical position-track row, and every
    block-table page's bytes (explicit copies — the decode/verify jits
    donate the device buffers these came from).  Holding the image makes
    resume bit-identical to never having been preempted: sampling folds
    (seed, position), drafts are deterministic per prefix, and attention
    only reaches page bytes through the block table, so physical
    re-placement on resume is invisible to the math."""

    req: Request
    tok: int
    pos: int
    gen_left: int
    temp: float
    topk: int
    keys: np.ndarray                   # (2,) uint32 sampling key
    pos_row: np.ndarray                # (max_len,) int32 position track
    payloads: list                     # per-page pool-leaf rows, bt order
    tel_carry: tuple                   # (drafted, accepted) already done


class ServeEngine:
    """Continuous-batching engine over a slotted KV cache.

    Drive it either with :meth:`run` (serve a whole trace, returns
    completions) or step-by-step with :meth:`submit` + :meth:`step` for
    integration into an async server loop.
    """

    def __init__(self, cfg, params, *, max_slots: int, max_len: int,
                 nldpe: NLDPEConfig = OFF, prefill_chunk: int = 16,
                 decode_block: int = 4, eos_id: int = -1,
                 batch_groups: int = 1,
                 kv_quant: str | None = None,
                 mesh=None, rules=None,
                 telemetry: "Telemetry | bool | None" = None,
                 prefill_buckets=None):
        bad = [t for t in cfg.layer_pattern if t not in ATTN_TYPES]
        if bad:
            raise NotImplementedError(
                f"continuous batching needs attention-block caches; "
                f"{cfg.name} pattern has {bad}")
        if prefill_chunk < 1 or decode_block < 1 or max_slots < 1:
            raise ValueError("max_slots, prefill_chunk, decode_block >= 1")
        prefill_chunk = min(prefill_chunk, max_len)
        # kv_quant selects the KV-cache storage grid (DESIGN.md §11):
        # "int8" = uniform absmax grid, "log8" = the drafter's sign-magnitude
        # log grid, None = keep cfg.kv_cache_dtype.  It is carried on the
        # config (the single source the cache init, spec trees, and
        # AttnSpec.kv_quant all read), so setting it here is exactly
        # dataclasses.replace(cfg, kv_cache_dtype=...).
        if kv_quant not in (None, "int8", "log8"):
            raise ValueError('kv_quant must be None, "int8", or "log8"')
        if kv_quant is not None:
            cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_quant)
        self.kv_quant = (cfg.kv_cache_dtype
                         if cfg.kv_cache_dtype in ("int8", "log8") else None)
        self.cfg = cfg
        # Mesh-sharded serving (DESIGN.md §9): with ``mesh`` set, params and
        # every cache/state leaf are placed per the logical-axis ``rules``
        # (a Rules table or a rules_for name; default "serve_exact" — heads
        # shard over "model", slots/pages over "data") and every per-tick
        # jit traces under the sharding context so in-model constraints
        # resolve.  Host-side scheduling stays global.  Under the default
        # exact rules, sharded outputs are bit-identical to mesh=None.
        self.mesh = mesh
        if isinstance(rules, str):
            rules = sharding.rules_for(rules, False)
        self.rules = rules if rules is not None \
            else sharding.serve_exact_rules()
        self.params = self._place_params(params)
        self.max_slots = max_slots
        self.max_len = max_len
        self.nldpe = nldpe
        self.prefill_chunk = prefill_chunk
        self.decode_block = decode_block
        self.eos_id = eos_id
        self.batch_groups = batch_groups
        # the KV cache is stored in the weights' dtype: bf16 weights serve
        # from a bf16 cache, f32 weights (the CPU test models) from f32
        self.dtype = params["embed"]["table"].dtype

        s = max_slots
        self.cache = self._place_cache(self._init_cache())
        slot_sh = self._named(("slots",), (s,))
        self._tok = self._put(jnp.zeros((s,), jnp.int32), slot_sh)
        self._pos = self._put(jnp.zeros((s,), jnp.int32), slot_sh)
        self._active = self._put(jnp.zeros((s,), bool), slot_sh)
        self._gen_left = self._put(jnp.zeros((s,), jnp.int32), slot_sh)
        self._temp = self._put(jnp.zeros((s,), jnp.float32), slot_sh)
        self._topk = self._put(jnp.zeros((s,), jnp.int32), slot_sh)
        self._keys = self._put(jnp.zeros((s, 2), jnp.uint32),
                               self._named(("slots", None), (s, 2)))

        self._slot_owner: list[Request | None] = [None] * s
        self._free = deque(range(s))
        self._out: dict[int, list[int]] = {}
        self._admitted_tick: dict[int, int] = {}
        # requests swapped out to host by priority preemption (the paged
        # engine populates this; the base run loop only has to know they
        # exist so a trace with everything preempted keeps running)
        self._preempted: list[_Preempted] = []
        self.tick = 0

        # observability (DESIGN.md §12).  The metrics registry is always
        # on: its group collectors are lazy closures over state the engine
        # maintains anyway, so registration costs nothing per tick.  Event
        # and latency telemetry is opt-in (``telemetry=True`` or an
        # instance); every call site below is guarded on it, and all of it
        # is host-side observation — enabling telemetry cannot change
        # emitted tokens (asserted across the differential matrix in
        # tests/test_engine_differential.py).
        if telemetry is True:
            telemetry = Telemetry()
        elif telemetry is False:
            telemetry = None
        self.telemetry = telemetry
        # rid -> (slot, drafted-at-admit, accepted-at-admit): lets finish
        # attribute per-request spec acceptance from the slot counters
        self._tel_admit: dict[int, tuple[int, int, int]] = {}
        self.metrics = MetricsRegistry()
        self.metrics.register_group("engine", self._engine_stats)
        if self.telemetry is not None:
            self.metrics.register_group("latency", self.telemetry.summary)

        # every jit below takes the params as an argument: a closed-over
        # array is embedded in the program as a constant, which at published
        # widths copies the weights into each executable
        self._chunk_fn = jax.jit(self._ctx(self._build_chunk_fn()),
                                 donate_argnums=(1,))
        self._decode_fn = jax.jit(self._ctx(self._build_decode_fn()),
                                  donate_argnums=(1, 2, 3, 4, 5))
        self._last_fn = jax.jit(self._ctx(_merge_last), donate_argnums=(0,))
        # first-token sampler, fixed (max_slots, V) shape so it compiles once
        self._sample_fn = jax.jit(self._ctx(
            lambda logits, keys, positions, temp, topk:
            sample_tokens(logits, step_keys(keys, positions), temp, topk)))
        # admission state writes as ONE fixed-shape masked merge (per-index
        # eager scatters re-specialize on every distinct wave size)
        self._state_fn = jax.jit(self._ctx(self._build_state_fn()),
                                 donate_argnums=tuple(range(7)))
        # post-tick active-mask snapshot in a FRESH buffer (no donation):
        # the next tick's decode donates the live ``_active`` buffer, so a
        # consumer materializing a tick's results after later dispatches
        # (the async drain thread) must not share it
        self._snap_fn = jax.jit(self._ctx(lambda a: jnp.logical_or(a, False)))
        # AOT-bucketed prefill (DESIGN.md §14): opt-in, off by default —
        # the per-chunk dispatch loop below stays the reference path
        self.prefill_pad_chunks = 0
        self._bucket_sizes: list[int] = []
        self._bucket_fns: dict[int, object] = {}
        self.aot_prefill = False
        if prefill_buckets:
            self._build_buckets(prefill_buckets)

    # ------------------------------------------------------------------
    # mesh placement (no-ops when mesh is None)
    # ------------------------------------------------------------------

    def _ctx(self, f):
        """Trace ``f`` under the engine's sharding context, so logical-axis
        ``shard(...)`` constraints inside the model resolve against
        (mesh, rules) — including the all-gather constraints at contraction
        boundaries that keep exact-rule sharding bit-identical, and the
        shard_map dispatch of the paged-attention kernel.  The wrapper
        keeps ``f``'s name, so a program is ``jit_decode`` or ``jit_chunk``
        in a device trace with a mesh or without one."""
        if self.mesh is None:
            return f
        mesh, rules = self.mesh, self.rules

        @functools.wraps(f)
        def traced(*args):
            with sharding_ctx(mesh, rules):
                return f(*args)

        return traced

    def _named(self, axes: tuple, shape: tuple):
        if self.mesh is None:
            return None
        return sharding.named(self.rules, axes, shape, self.mesh)

    @staticmethod
    def _put(x, sh):
        return x if sh is None else jax.device_put(x, sh)

    def _place_params(self, params):
        """Place every parameter leaf per the rule table (spec-mode init
        mirrors the param pytree without materializing arrays)."""
        if self.mesh is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec
        from ..nn.module import spec_mode
        with spec_mode(self.mesh, self.rules):
            pspecs = lm.init_params(jax.random.key(0), self.cfg)
        shardings = jax.tree.map(
            lambda p: NamedSharding(self.mesh, p), pspecs,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        return jax.device_put(params, shardings)

    def _cache_pspecs(self):
        return lm.cache_pspecs(self.cfg, self.max_slots, self.max_len,
                               self.mesh, self.rules, slotted=True,
                               ring_slack=self.prefill_chunk - 1)

    def _place_cache(self, cache):
        """Give every cache leaf (K/V pools, pos tracks, block tables) its
        ``cache_pspecs`` sharding: kv-heads over "model", slots over
        "data", pages replicated per the serve tables."""
        if self.mesh is None:
            return cache
        from jax.sharding import NamedSharding, PartitionSpec
        shardings = jax.tree.map(
            lambda p: NamedSharding(self.mesh, p), self._cache_pspecs(),
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        return jax.device_put(cache, shardings)

    def _init_cache(self):
        # windowed rings get prefill_chunk-1 slack lines: a chunk's writes
        # land before its queries attend, so the chunk's first query still
        # needs the full window behind it (see nn.attention.init_cache)
        return lm.init_model_cache(self.cfg, self.max_slots, self.max_len,
                                   dtype=self.dtype, slotted=True,
                                   ring_slack=self.prefill_chunk - 1)

    def _release_slot(self, sl: int, seq: tuple | None = None) -> None:
        """Hook: a slot's request finished (subclasses release its pages).
        ``seq`` is the request's *committed* token sequence — prompt plus
        every generated token whose K/V was written (i.e. all but the last)
        — or None when there is nothing beyond the admission-time state."""

    # ------------------------------------------------------------------
    # jit'd building blocks
    # ------------------------------------------------------------------

    @staticmethod
    def _clip_pos(cache, mask, bound):
        """On masked slots, make every cache line at position >= bound
        never-valid (pos <- -1).  bound is () or (S,)."""
        return clip_positions(cache, mask, bound)

    def _build_chunk_fn(self):
        cfg, nldpe, groups = self.cfg, self.nldpe, self.batch_groups
        c = self.prefill_chunk

        def chunk(params, cache, tokens, base_pos, mask, limit):
            """One (max_slots, prefill_chunk) prefill chunk, shared offsets,
            per-slot write masks.

            Pre-clear: stale position entries >= base_pos on writing slots
            (the previous tenant's lines) become never-valid before the
            chunk attends — chunk 0 wipes the whole track.  Post-clip:
            entries >= limit (= min(real_len, chunk end)) go never-valid,
            trimming the padded prompt tail.  Folding both into the chunk
            call keeps admission at one jit dispatch per chunk.
            """
            cache = ServeEngine._clip_pos(cache, mask, base_pos)
            positions = base_pos + jnp.arange(c, dtype=jnp.int32)
            logits, cache = lm.forward(params, tokens, cfg, mode="chunk",
                                       cache=cache, positions=positions,
                                       nldpe=nldpe, batch_groups=groups,
                                       write_mask=mask)
            return logits, ServeEngine._clip_pos(cache, mask, limit)

        return chunk

    def _chunk_base(self, reuse, i: int):
        """Chunk ``i``'s base-position argument for the chunk fn: a shared
        scalar for the slotted engine (every admitted slot prefills at the
        same offsets; ``reuse`` is always zero).  The paged engine
        overrides with per-slot reuse-shifted vectors.  Works on host
        arrays and traced arrays alike, so the eager chunk loop and the
        in-graph bucket fn share it."""
        del reuse
        return jnp.int32(i * self.prefill_chunk)

    def _build_bucket_fn(self, n: int):
        """One prefill bucket: a whole admission wave's ``n``-chunk
        sequence as ONE traced computation.  The per-chunk write masks,
        base offsets, and clip limits the host dispatch loop computes are
        derived in-graph from the wave's (admit, reuse, plen) vectors, so
        a single executable serves every wave padded to this bucket —
        padded chunks carry all-False write masks and leave the cache
        bit-unchanged (write_mask gates every K/V scatter and both
        position clips are masked no-ops)."""
        chunk = self._build_chunk_fn()
        c, s, v = self.prefill_chunk, self.max_slots, self.cfg.vocab_size

        def bucket(params, cache, tokens, admit, reuse, plen, ci, col):
            suffix = plen - reuse
            last = jnp.zeros((s, v), jnp.float32)
            for i in range(n):
                mask = admit & (i * c < suffix)
                limit = jnp.minimum(plen, reuse + (i + 1) * c)
                lg, cache = chunk(
                    params, cache,
                    jax.lax.slice_in_dim(tokens, i * c, (i + 1) * c, axis=1),
                    self._chunk_base(reuse, i), mask, limit)
                last = _merge_last(last, lg, admit & (ci == i), col)
            return last, cache

        return bucket

    def _build_buckets(self, spec) -> None:
        """AOT-compile the prefill bucket table (DESIGN.md §14).

        ``spec`` is True — power-of-two chunk counts up to
        ceil(max_len / prefill_chunk) — or an iterable of chunk counts;
        the maximal bucket is always appended so every wave fits.  Each
        bucket compiles at construction via ``jit(...).lower().compile()``
        so the first admission of any prompt length pays zero compile
        latency.  Sharded engines keep lazily-compiled jits per bucket
        (input placement is decided by the sharding context at the first
        call); the bucket *padding* semantics are identical either way.
        """
        c, s = self.prefill_chunk, self.max_slots
        n_max = -(-self.max_len // c)
        if spec is True:
            sizes, n = [], 1
            while n < n_max:
                sizes.append(n)
                n *= 2
            sizes.append(n_max)
        else:
            sizes = sorted({min(max(1, int(b)), n_max) for b in spec})
            if not sizes or sizes[-1] != n_max:
                sizes.append(n_max)
        self._bucket_sizes = sizes
        param_avals, cache_avals = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            (self.params, self.cache))
        vec = jax.ShapeDtypeStruct((s,), jnp.int32)
        adm = jax.ShapeDtypeStruct((s,), jnp.bool_)
        self.aot_prefill = self.mesh is None
        for n in sizes:
            fn = jax.jit(self._ctx(self._build_bucket_fn(n)),
                         donate_argnums=(1,))
            if self.aot_prefill:
                toks = jax.ShapeDtypeStruct((s, n * c), jnp.int32)
                fn = fn.lower(param_avals, cache_avals, toks, adm, vec, vec,
                              vec, vec).compile()
            self._bucket_fns[n] = fn

    def _prefill_chunks(self, admit, plen_np, reuse_np, tokens,
                        ci_np, col_np):
        """Dispatch one admission wave's chunked prefill; returns the
        merged (S, V) last-token logits and the dispatched chunk count.

        Default: one jit dispatch per chunk (the reference path).  With
        ``prefill_buckets`` the wave pads to the smallest covering bucket
        and runs as a single AOT-compiled call — the padded chunks are
        write-masked off for every slot, so cache bytes and sampled tokens
        are bit-identical to the per-chunk loop."""
        s, c = self.max_slots, self.prefill_chunk
        suffix = plen_np - reuse_np
        n_chunks = -(-int(suffix[admit].max()) // c)
        if self._bucket_fns:
            nb = min(b for b in self._bucket_sizes if b >= n_chunks)
            pad = tokens
            if nb * c > tokens.shape[1]:
                pad = np.zeros((s, nb * c), np.int32)
                pad[:, :tokens.shape[1]] = tokens
            self.prefill_pad_chunks += nb - n_chunks
            last, self.cache = self._bucket_fns[nb](
                self.params, self.cache, jnp.asarray(pad), jnp.asarray(admit),
                jnp.asarray(reuse_np), jnp.asarray(plen_np),
                jnp.asarray(ci_np), jnp.asarray(col_np))
            return last, nb
        col_j = jnp.asarray(col_np)
        last = jnp.zeros((s, self.cfg.vocab_size), jnp.float32)
        for i in range(n_chunks):
            mask = jnp.asarray(admit & (i * c < suffix))
            limit = np.minimum(plen_np, reuse_np + (i + 1) * c)
            lg, self.cache = self._chunk_fn(
                self.params, self.cache,
                jnp.asarray(tokens[:, i * c:(i + 1) * c]),
                self._chunk_base(reuse_np, i), mask,
                jnp.asarray(limit.astype(np.int32)))
            last = self._last_fn(last, lg,
                                 jnp.asarray(admit & (ci_np == i)), col_j)
        return last, n_chunks

    def _build_state_fn(self):
        def apply_state(tok, pos, active, gen_left, temp, topk, keys,
                        sel, n_tok, n_pos, n_gen, n_temp, n_topk, n_keys):
            m = sel
            return (jnp.where(m, n_tok, tok), jnp.where(m, n_pos, pos),
                    active | m, jnp.where(m, n_gen, gen_left),
                    jnp.where(m, n_temp, temp), jnp.where(m, n_topk, topk),
                    jnp.where(m[:, None], n_keys, keys))
        return apply_state

    def _build_decode_fn(self):
        cfg, nldpe, groups = self.cfg, self.nldpe, self.batch_groups
        eos, block = self.eos_id, self.decode_block

        def decode(params, cache, tok, pos, active, gen_left, temp, topk,
                   keys):
            def step(carry, _):
                cache, tok, pos, active, gen_left = carry
                logits, cache = lm.decode_step(
                    params, cfg, tok, pos, cache, nldpe=nldpe,
                    batch_groups=groups, write_mask=active)
                nxt = sample_tokens(logits, step_keys(keys, pos + 1),
                                    temp, topk)
                emit = jnp.where(active, nxt, -1)
                gen_left = gen_left - active.astype(jnp.int32)
                done = gen_left <= 0
                if eos >= 0:
                    done = done | (nxt == eos)
                tok = jnp.where(active, nxt, tok)
                pos = pos + active.astype(jnp.int32)
                active = active & ~done
                return (cache, tok, pos, active, gen_left), emit

            carry, emits = jax.lax.scan(
                step, (cache, tok, pos, active, gen_left), None, length=block)
            return carry + (emits,)

        return decode

    # ------------------------------------------------------------------
    # admission: one wave = reset -> masked chunk calls -> trim -> sample
    # ------------------------------------------------------------------

    def _validate(self, req: Request):
        """Reject degenerate requests at admission with a clear error —
        inside the jit'd chunk fn they would silently clamp (OOB embedding
        gathers, dropped scatters) and produce garbage tokens instead."""
        p = len(req.tokens)
        if p < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens="
                f"{req.max_new_tokens} <= 0 (nothing to generate)")
        if p > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {p} > max_len="
                f"{self.max_len} (prompt alone overflows the KV cache)")
        bad = [t for t in req.tokens
               if not (0 <= int(t) < self.cfg.vocab_size)]
        if bad:
            raise ValueError(
                f"request {req.rid}: token ids {bad[:4]} outside "
                f"[0, vocab_size={self.cfg.vocab_size}) — the embedding "
                f"gather would clamp them silently")
        if req.top_k < 0:
            raise ValueError(f"request {req.rid}: top_k={req.top_k} < 0")
        if TOP_K_CAP < req.top_k < self.cfg.vocab_size:
            # top_k >= vocab_size explicitly disables the restriction
            # (sampling.process_logits); anything between the static
            # gather cap and the vocabulary cannot be represented and
            # would silently clamp to TOP_K_CAP inside the jit
            raise ValueError(
                f"request {req.rid}: top_k={req.top_k} exceeds "
                f"TOP_K_CAP={TOP_K_CAP} (the static sampler gather width) "
                f"but is below vocab_size={self.cfg.vocab_size}; use "
                f"top_k <= {TOP_K_CAP}, or >= vocab_size to disable top-k")
        if not (req.temperature >= 0 and math.isfinite(req.temperature)):
            # catches NaN (comparison false), -inf/+inf, and negatives:
            # 0 already means greedy, so anything below is a caller bug,
            # and +inf would sample near-uniformly from the top-k set
            raise ValueError(
                f"request {req.rid}: temperature={req.temperature} must "
                f"be finite and >= 0 (0 -> greedy)")
        if req.rid in self._out:
            raise ValueError(f"request {req.rid}: rid already in flight")
        need = p + req.max_new_tokens - 1
        if need > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt {p} + {req.max_new_tokens} new "
                f"tokens needs {need} positions > max_len={self.max_len}")

    def _check_wave(self, reqs: list[Request]) -> None:
        assert len(reqs) <= self.free_slots
        rids = [r.rid for r in reqs]
        if len(set(rids)) != len(rids):
            raise ValueError(f"duplicate rids in one admission wave: {rids}")
        for r in reqs:
            self._validate(r)

    def _admit_wave(self, reqs: list[Request]) -> list[Completion]:
        """Admit up to ``free_slots`` requests in one batched prefill."""
        self._check_wave(reqs)
        tel = self.telemetry
        s, c = self.max_slots, self.prefill_chunk
        with span(tel, "admission_wave", self.tick,
                  rids=[r.rid for r in reqs], n_reqs=len(reqs)) as wave:
            with span(tel, "plan", self.tick):
                slots = [self._free.popleft() for _ in reqs]
                admit = np.zeros((s,), bool)
                plen = np.ones((s,), np.int32)   # 1 avoids 0-len edge cases
                for r, sl in zip(reqs, slots):
                    admit[sl] = True
                    plen[sl] = len(r.tokens)
                n_chunks = -(-int(plen[admit].max()) // c)
                tokens = np.zeros((s, n_chunks * c), np.int32)
                # per-slot (chunk, column) of the last real prompt token
                ci_np = np.zeros((s,), np.int32)
                col_np = np.zeros((s,), np.int32)
                keys_np = np.zeros((s, 2), np.uint32)
                temp_np = np.zeros((s,), np.float32)
                topk_np = np.zeros((s,), np.int32)
                for r, sl in zip(reqs, slots):
                    tokens[sl, :len(r.tokens)] = r.tokens
                    ci_np[sl] = (len(r.tokens) - 1) // c
                    col_np[sl] = (len(r.tokens) - 1) % c
                    keys_np[sl] = np.asarray(
                        request_key(r.seed if r.seed is not None else r.rid))
                    temp_np[sl] = r.temperature
                    topk_np[sl] = r.top_k
            with span(tel, "prefill", self.tick):
                last, wave["n_chunks"] = self._prefill_chunks(
                    admit, plen, np.zeros((s,), np.int32), tokens, ci_np,
                    col_np)
            return self._seat(reqs, slots, last, keys_np, plen, temp_np,
                              topk_np, [(0, 0)] * len(reqs))

    def _seat(self, reqs, slots, last, keys_np, plen_np, temp_np, topk_np,
              notes) -> list[Completion]:
        """The end of every admission wave: sample and sync the first
        tokens, record them, retire instant finishes (releasing their
        slots), and merge decode state for the rest in one jit.  ``notes``
        is each request's (prefix reuse, pages held) for its record."""
        tel = self.telemetry
        s = self.max_slots
        with span(tel, "first_token_sync", self.tick):
            all_firsts = np.asarray(self._sample_fn(
                last, jnp.asarray(keys_np), jnp.asarray(plen_np),
                jnp.asarray(temp_np), jnp.asarray(topk_np)))
        done: list[Completion] = []
        sel = np.zeros((s,), bool)
        n_tok = np.zeros((s,), np.int32)
        n_pos = np.zeros((s,), np.int32)
        n_gen = np.zeros((s,), np.int32)
        n_temp = np.zeros((s,), np.float32)
        n_topk = np.zeros((s,), np.int32)
        n_keys = np.zeros((s, 2), np.uint32)
        for r, sl, (reuse, pages) in zip(reqs, slots, notes):
            first = int(all_firsts[sl])
            self._out[r.rid] = [first]
            self._admitted_tick[r.rid] = self.tick
            if tel is not None:
                self._tel_note_admit(r, sl, reuse=reuse, pages_held=pages)
            if r.max_new_tokens == 1 or (self.eos_id >= 0
                                         and first == self.eos_id):
                self._release_slot(sl)
                self._free.appendleft(sl)
                done.append(self._complete(
                    r, "eos" if first == self.eos_id else "length"))
                continue
            self._slot_owner[sl] = r
            sel[sl] = True
            n_tok[sl] = first
            n_pos[sl] = len(r.tokens)
            n_gen[sl] = r.max_new_tokens - 1
            n_temp[sl] = r.temperature
            n_topk[sl] = r.top_k
            n_keys[sl] = keys_np[sl]

        if sel.any():
            with span(tel, "merge", self.tick):
                (self._tok, self._pos, self._active, self._gen_left,
                 self._temp, self._topk, self._keys) = self._state_fn(
                    self._tok, self._pos, self._active, self._gen_left,
                    self._temp, self._topk, self._keys, jnp.asarray(sel),
                    jnp.asarray(n_tok), jnp.asarray(n_pos),
                    jnp.asarray(n_gen), jnp.asarray(n_temp),
                    jnp.asarray(n_topk), jnp.asarray(n_keys))
        return done

    @staticmethod
    def _priority_order(waiting: deque) -> None:
        """Stable-reorder the waiting queue by descending priority (FIFO
        within each class) — shared by both engines' wave selection.  A
        no-op on all-default-priority traffic, so priority-free traces
        schedule exactly as before."""
        if any(r.priority for r in waiting):
            ordered = sorted(waiting, key=lambda r: -r.priority)
            waiting.clear()
            waiting.extend(ordered)

    def _resume_preempted(self, waiting=()) -> None:
        """Hook: swap preempted requests back in (paged engine).  Runs
        each scheduler iteration before admission; ``waiting`` lets the
        override defer resumes that higher-priority arrivals would only
        preempt again."""

    def _can_admit(self, waiting: deque) -> bool:
        """Whether ``_select_wave`` could admit anything right now.  The
        paged engine also answers True with zero free slots when a
        waiting request outranks an active one (admission by
        preemption)."""
        return bool(self._free)

    def _select_wave(self, waiting: deque) -> list[Request]:
        """Pop the next admission wave off the waiting queue (subclasses
        add resource admission control, e.g. page availability)."""
        self._priority_order(waiting)
        return [waiting.popleft()
                for _ in range(min(len(waiting), len(self._free)))]

    def submit(self, req: Request) -> Completion | None:
        """Admit one request into a free slot (raises if none are free).
        Returns a Completion immediately if it finished at admission."""
        if not self._free:
            raise RuntimeError("no free slot; check free_slots before submit")
        self._validate(req)
        if self.telemetry is not None:
            self.telemetry.enqueue(req.rid, self.tick)
        done = self._admit_wave([req])
        return done[0] if done else None

    def _complete(self, req: Request, reason: str) -> Completion:
        comp = Completion(rid=req.rid, prompt=tuple(req.tokens),
                          tokens=self._out.pop(req.rid),
                          finish_reason=reason,
                          admitted_tick=self._admitted_tick.pop(req.rid),
                          finished_tick=self.tick)
        tel = self.telemetry
        if tel is not None:
            sl, d0, a0 = self._tel_admit.pop(req.rid, (None, 0, 0))
            drafted = accepted = 0
            dr = getattr(self, "_drafted", None)
            if sl is not None and dr is not None:
                drafted = int(dr[sl]) - d0
                accepted = int(self._accepted[sl]) - a0
            tel.finish(req.rid, self.tick, reason=reason,
                       n_tokens=len(comp.tokens), drafted=drafted,
                       accepted=accepted)
        return comp

    # ------------------------------------------------------------------
    # decode tick + trace scheduler
    # ------------------------------------------------------------------

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def any_active(self) -> bool:
        return any(o is not None for o in self._slot_owner)

    def _engine_stats(self) -> dict:
        """Scheduler-level gauges for ``metrics.snapshot()["engine"]``.
        Everything here is host state — reading it never syncs a device
        array (``_slot_owner``, not ``_active``, carries occupancy)."""
        return {"tick": self.tick, "free_slots": self.free_slots,
                "active_slots": sum(o is not None
                                    for o in self._slot_owner),
                "inflight": len(self._out),
                "prefill_buckets": len(self._bucket_sizes),
                "prefill_pad_chunks": self.prefill_pad_chunks}

    def _tel_note_admit(self, r: Request, sl: int, *, reuse: int = 0,
                        pages_held: int = 0) -> None:
        """Record one admission (called only with telemetry enabled):
        lifecycle edges plus a snapshot of the slot's cumulative spec
        counters, so finish can attribute per-request drafted/accepted as
        a delta even though the engine only keeps per-slot totals."""
        tel = self.telemetry
        dr = getattr(self, "_drafted", None)
        self._tel_admit[r.rid] = (
            sl, 0 if dr is None else int(dr[sl]),
            0 if dr is None else int(self._accepted[sl]))
        tel.admit(r.rid, self.tick, slot=sl, prompt_len=len(r.tokens),
                  reuse=reuse, pages_held=pages_held)
        # the request's first token is sampled at the end of its
        # admission wave — this call sits right after that sample
        tel.first_token(r.rid, self.tick)

    def _dispatch_tick(self):
        """Device work of one decode tick, no host-side harvest.

        Dispatches the scanned decode jit and returns ``(emits, active,
        tick)``: the (T, S) emitted-token buffer, the post-tick active mask
        in a freshly allocated buffer (the next tick donates the live
        one), and ``tick`` — the tick's open ``decode_block`` span, which
        :meth:`_collect` closes once the tokens are harvested (None with
        telemetry off).  :meth:`step` materializes inline; the async engine
        hands the triple to its drain thread so device dispatch never
        blocks on host work."""
        tel = self.telemetry
        tick = None
        if tel is not None:
            tel.tick_boundary(self.tick)
            tick = tel.trace.open(
                "decode_block", self.tick, block=self.decode_block,
                n_active=sum(o is not None for o in self._slot_owner))
        with span(tel, "dispatch", self.tick, parent=tick and tick.id):
            (self.cache, self._tok, self._pos, self._active, self._gen_left,
             emits) = self._decode_fn(self.params, self.cache, self._tok,
                                      self._pos, self._active,
                                      self._gen_left, self._temp, self._topk,
                                      self._keys)
            self.tick += self.decode_block
            active = self._snap_fn(self._active)
        if tick is not None:
            tick.tick = self.tick
        return emits, active, tick

    def step(self) -> list[Completion]:
        """One decode tick: ``decode_block`` scanned steps over all slots.
        Returns the requests that finished during the tick."""
        emits, active, tick = self._dispatch_tick()
        with span(self.telemetry, "drain", self.tick,
                  parent=tick and tick.id):
            emits = np.asarray(emits)   # the tick's one existing host sync
            active = np.asarray(active)
        return self._collect(emits, active, tick)

    def _collect(self, emits: np.ndarray, active: np.ndarray,
                 tick) -> list[Completion]:
        """:meth:`_harvest` one materialized tick inside a ``harvest`` span
        that records the tokens each request received, then close the
        tick's ``decode_block`` span (``tick``, None with telemetry off or
        for a speculative tick)."""
        tel = self.telemetry
        if tel is None:
            return self._harvest(emits, active)
        owners = [(s, r.rid) for s, r in enumerate(self._slot_owner)
                  if r is not None]
        got = (emits >= 0).sum(axis=0)
        with tel.trace.span("harvest", self.tick, parent=tick and tick.id,
                            rids=[rid for _, rid in owners],
                            n_emitted=[int(got[s]) for s, _ in owners]):
            done = self._harvest(emits, active)
        if tick is not None:
            tel.trace.close(tick)
        return done

    def _harvest(self, emits: np.ndarray,
                 active: np.ndarray) -> list[Completion]:
        """Fold one tick's emitted tokens (T, S), -1 = no token, into the
        per-request outputs and retire slots that went inactive.  ``active``
        is that tick's post-dispatch mask snapshot — passed in, not read
        from ``self._active``, because under the async pipeline later
        ticks may already have advanced (and donated) the live state."""
        done: list[Completion] = []
        for s, req in enumerate(self._slot_owner):
            if req is None:
                continue
            toks = emits[:, s]
            self._out[req.rid].extend(int(t) for t in toks if t >= 0)
            if not active[s]:
                last = self._out[req.rid][-1]
                reason = ("eos" if self.eos_id >= 0 and last == self.eos_id
                          else "length")
                comp = self._complete(req, reason)
                done.append(comp)
                self._slot_owner[s] = None
                # committed sequence: every position with written K/V —
                # the prompt plus all generated tokens but the last
                self._release_slot(s, seq=comp.prompt
                                   + tuple(comp.tokens[:-1]))
                self._free.append(s)
        return done

    def run(self, requests: list[Request]) -> list[Completion]:
        """Serve a whole trace: admit each request at its ``arrival`` tick
        (or as soon after as a slot frees up), decode continuously, return
        completions sorted by rid."""
        queue = deque(sorted(requests, key=lambda r: r.arrival))
        waiting: deque[Request] = deque()
        completions: list[Completion] = []
        tel = self.telemetry
        while queue or waiting or self.any_active or self._preempted:
            progressed = False
            while queue and queue[0].arrival <= self.tick:
                r = queue.popleft()
                if tel is not None:
                    tel.enqueue(r.rid, r.arrival)
                waiting.append(r)
                progressed = True
            n_pre = len(self._preempted)
            self._resume_preempted(waiting)
            progressed |= len(self._preempted) != n_pre
            if waiting and self._can_admit(waiting):
                wave = self._select_wave(waiting)
                if wave:
                    completions.extend(self._admit_wave(wave))
                    progressed = True
            if not self.any_active:
                if progressed:
                    continue        # instant finishes freed slots; re-admit
                if queue:
                    # idle until the next arrival — this strictly advances
                    # the tick (an arrival <= tick would have moved to
                    # waiting above), so the loop cannot spin here even
                    # with a non-empty waiting queue whose admission is
                    # blocked: future arrivals still get their chance
                    self.tick = max(self.tick, queue[0].arrival)
                    continue
                # nothing active, nothing arriving, and this iteration
                # moved nothing: no future iteration can differ — a
                # stall, not a schedule; never spin silently
                if waiting:
                    raise RuntimeError(
                        f"scheduler deadlock: {len(waiting)} waiting and "
                        f"{len(self._preempted)} preempted request(s), no "
                        f"active slots, no future arrivals, and admission "
                        f"made no progress (admission blocked or the pool "
                        f"is too small for the requests)")
                if self._preempted:
                    # resume into a fully idle engine just failed: the
                    # pool cannot hold the preempted footprints
                    raise RuntimeError(
                        f"{len(self._preempted)} preempted request(s) "
                        f"cannot resume into an idle engine; the page "
                        f"pool is too small for their footprints")
                break
            completions.extend(self.step())
        return sorted(completions, key=lambda c: c.rid)


class PagedServeEngine(ServeEngine):
    """Continuous batching over a **paged** KV cache with radix prefix
    sharing (DESIGN.md §7).

    Physical KV storage is a pool of ``num_pages`` fixed-size pages per
    layer (``launch/kvpool.py`` owns the metadata; one page id addresses
    every layer's pool row), and each slot maps logical blocks onto pages
    through a block-table row.  Two things fall out:

    * **capacity decouples from ``max_slots * max_len``** — pages are
      allocated for a request's *actual* ``prompt + gen`` footprint, so a
      smaller pool oversubscribes slots (admission waits for pages instead
      of slots) and a larger one retains finished prompts as reusable
      cache;
    * **shared prefixes prefill once** — admission walks the radix index;
      every fully-matched page is mapped read-only into the new slot's
      block table and its prefill is skipped.  Only the suffix (always
      including the final prompt token, whose logits seed sampling) runs
      through chunked prefill, at per-slot chunk offsets.

    Copy-on-write: when cached pages cover the *whole* prompt, the
    boundary page is forked (one device-side page copy) so recomputing the
    final token and appending decode K/V never mutates the shared
    original.  Shared pages are therefore read-only by construction and
    the jit'd compute is oblivious to sharing.

    Determinism contract: outputs are **bit-exact** with the slotted
    ``ServeEngine`` on any trace, OFF and NL-DPE-fused — attention runs on
    the gathered dense view (``nn.attention.paged_dense_view``), which
    reproduces the slotted cache's score rows exactly (prefix-hit pages
    hold bit-identical K/V because K/V at a position depend only on the
    token prefix and the exp-grid anchors to the fixed cache length; see
    DESIGN.md §7 and tests/test_paged_engine*.py).

    **Speculative decoding** (``spec_k > 0``, DESIGN.md §8): each decode
    tick drafts ``spec_k`` tokens per slot through the NL-DPE
    low-precision path (``spec_draft`` numerics over log-quant-programmed
    weights — ``launch/spec_decode.py``) and verifies all ``spec_k + 1``
    positions in ONE exact chunk pass with standard rejection sampling.
    Greedy outputs stay token-for-token identical to ``spec_k=0`` (the
    verify chunk is bit-equal to sequential decode); sampled outputs keep
    the target distribution via the leftover-distribution correction.
    Rejected positions roll back by position-track clip; the radix index
    only ever sees *committed* tokens (``kvpool.publish_committed``), and
    completed generations are published as reusable prefix cache
    (``cache_generations``).  The live acceptance rate (``spec_stats``) is
    the analog-fidelity signal.
    """

    def __init__(self, cfg, params, *, max_slots: int, max_len: int,
                 nldpe: NLDPEConfig = OFF, prefill_chunk: int = 16,
                 decode_block: int = 4, eos_id: int = -1,
                 batch_groups: int = 1,
                 page_size: int = 16, num_pages: int | None = None,
                 host_cache_pages: int = 0,
                 spec_k: int = 0, spec_draft: NLDPEConfig | None = None,
                 cache_generations: bool = True,
                 drift: DriftInjection | None = None,
                 fidelity: FidelityPolicy | None = None,
                 kv_quant: str | None = None,
                 mesh=None, rules=None,
                 telemetry: "Telemetry | bool | None" = None,
                 prefill_buckets=None):
        if "local" in cfg.layer_pattern:
            raise NotImplementedError(
                "paged KV cache needs non-windowed attention layers: ring "
                "wrap history would break prefix sharing (got 'local')")
        if page_size < 1:
            raise ValueError("page_size >= 1")
        if spec_k < 0:
            raise ValueError("spec_k >= 0 (0 disables speculation)")
        self.page_size = page_size
        self.n_blocks = -(-max_len // page_size)
        if num_pages is None:
            num_pages = max_slots * self.n_blocks    # slotted-parity default
        self.num_pages = num_pages
        # host spill tier (DESIGN.md §13): with host_cache_pages > 0, LRU
        # eviction demotes refcount-0 radix pages to host RAM instead of
        # destroying them; radix hits on spilled nodes restore host→device
        # before publish.  0 keeps the destroy-on-evict behavior exactly.
        self.host_cache_pages = int(host_cache_pages)
        self.pool = PagePool(num_pages, page_size,
                             host_pages=self.host_cache_pages)
        # the radix root is keyed by byte semantics: NL-DPE numerics AND
        # the KV storage grid — a quantized pool's pages must never be
        # prefix-hit by an fp pool (or "int8" by "log8") for the same
        # prompt, their bytes mean different things
        if kv_quant not in (None, "int8", "log8"):
            raise ValueError('kv_quant must be None, "int8", or "log8"')
        eff_quant = kv_quant or (cfg.kv_cache_dtype
                                 if cfg.kv_cache_dtype in ("int8", "log8")
                                 else None)
        self._fp = nldpe_fingerprint(nldpe, eff_quant)
        self._slot_pages: list[list[int] | None] = [None] * max_slots
        self.spec_k = int(spec_k)
        # drafter numerics: full analog path by default (log-domain DMMul +
        # ACAM softmax); enabled=False keeps only the conductance-programmed
        # weights (cheap to simulate, still int8/log-quant numerics)
        self.spec_draft = (spec_draft if spec_draft is not None
                          else NLDPEConfig(enabled=True))
        self.cache_generations = cache_generations
        super().__init__(cfg, params, max_slots=max_slots, max_len=max_len,
                         nldpe=nldpe, prefill_chunk=prefill_chunk,
                         decode_block=decode_block, eos_id=eos_id,
                         batch_groups=batch_groups,
                         kv_quant=kv_quant, mesh=mesh, rules=rules,
                         telemetry=telemetry, prefill_buckets=prefill_buckets)
        self._setup_fn = jax.jit(self._ctx(self._build_setup_fn()),
                                 donate_argnums=(0,))
        self._copy_fn = jax.jit(self._ctx(self._build_copy_fn()),
                                donate_argnums=(0,))
        # tier plumbing: one page's pool-leaf rows out of / into the cache
        # (nn.attention helpers; every kv_quant mode), the canonical pos
        # row of one slot, the resume-time bt+pos rewrite, and the
        # preempt-time active-bit clear.  Gather never donates (reads the
        # live cache); scatter/resume/deact donate like every cache write.
        from ..nn.attention import gather_page_rows, scatter_page_rows
        self._gather_fn = jax.jit(self._ctx(gather_page_rows))
        self._scatter_fn = jax.jit(self._ctx(scatter_page_rows),
                                   donate_argnums=(0,))
        self._pos_row_fn = jax.jit(self._ctx(self._build_pos_row_fn()))
        self._resume_fn = jax.jit(self._ctx(self._build_resume_fn()),
                                  donate_argnums=(0,))
        self._deact_fn = jax.jit(self._ctx(lambda active, m: active & ~m),
                                 donate_argnums=(0,))
        if self.host_cache_pages > 0:
            self.pool.on_spill = self._spill_page
        self.preempts = 0
        self.resumes = 0
        if (drift is not None or fidelity is not None) and not spec_k:
            raise ValueError(
                "drift/fidelity act on the analog draft path; they need "
                "spec_k > 0")
        if self.spec_k:
            # the drafter's weights: the target parameters round-tripped
            # through the 8-bit log grid (programmed conductances), cached
            # on device once — no second model to train or store.  Draft
            # and verify are two jits per step: two hardware units (analog
            # engine / digital verifier), and the boundary lets the engine
            # meter the analog phase's wall share exactly.  Quantizing
            # self.params (not the raw argument) keeps the drafter's
            # weights on the engine's mesh placement.
            self._draft_params = quantize_draft_params(self.params)
            # (draft, verify) jit pairs cached per live depth: the draft
            # scan length and verify chunk width are trace constants, so
            # the fidelity ladder's spec_k moves swap compiled functions
            # instead of retracing.  self.spec_k stays the *planning*
            # depth (_plan budgets its page slack), and spec_k_live only
            # ever moves below it — shrinking is slack-safe, growing past
            # it would not be.
            self._spec_fn_cache: dict[int, tuple] = {}
            self.spec_k_live = self.spec_k
            self._spec_steps = 0
            self._drafted = np.zeros((max_slots,), np.int64)
            self._accepted = np.zeros((max_slots,), np.int64)
            self.spec_draft_seconds = 0.0
            # windowed acceptance (satellite of the fidelity loop, useful
            # standalone): counters since the last reset_window(), plus a
            # per-tick EWMA — lifetime totals cannot see degradation
            self._win_drafted = np.zeros((max_slots,), np.int64)
            self._win_accepted = np.zeros((max_slots,), np.int64)
            self._win_ticks = 0
            self.ewma_acceptance: float | None = None
            self._spec_fns_for(self.spec_k)     # warm the default depth
        # closed-loop fidelity (DESIGN.md §10): drift = the plant (aging
        # device model on a virtual clock), monitor = the controller
        # (acceptance-driven degradation ladder); either works alone
        self.drift = drift
        self.monitor = (FidelityMonitor(fidelity or FidelityPolicy(), spec_k)
                        if drift is not None or fidelity is not None
                        else None)
        self._ewma_alpha = (self.monitor.policy.ewma_alpha
                            if self.monitor is not None else 0.25)
        self.vclock = 0.0               # virtual seconds; never wall-clock
        self._downtime_s = 0.0
        self._reprograms = 0
        self._disabled_ticks = 0
        if drift is not None:
            pkey, self._drift_key, self._read_key = jax.random.split(
                jax.random.key(drift.seed), 3)
            m = drift.model
            self._drift_state = drift_lib.program_params(
                pkey, self._draft_params, m)
            if drift.read_noise:
                self._read_fn = jax.jit(self._ctx(
                    lambda st, t, k: drift_lib.read_params(st, m, t,
                                                           read_key=k)))
            else:
                self._read_fn = jax.jit(self._ctx(
                    lambda st, t: drift_lib.read_params(st, m, t)))
            self._reprogram_fn = jax.jit(
                lambda k, st, q, t: drift_lib.reprogram_params(k, st, q,
                                                               m, t))

        # registry groups superseding the three legacy stats dicts
        # (deprecation-shim contract, tests/test_telemetry.py: each group
        # snapshot compares == to its dict); collectors are lazy, so they
        # may reference monitor/drift state initialized just above
        self.metrics.register_group("pool", lambda: dict(self.pool.stats))
        self.metrics.register_group("spec", lambda: self.spec_stats)
        self.metrics.register_group("fidelity", lambda: self.fidelity_stats)
        # tier gauges live in their own group: the "pool" group must stay
        # == dict(pool.stats) (deprecation-shim contract)
        self.metrics.register_group("tiers", lambda: {
            "host_pages": self.pool.host_pages,
            "host_used": self.pool.host_used,
            "preempted_waiting": len(self._preempted),
            "preempts": self.preempts, "resumes": self.resumes})
        tel = self.telemetry
        if tel is not None:
            self.pool.on_evict = (
                lambda page: tel.event("eviction", self.tick, page=page))

    def _init_cache(self):
        return lm.init_model_cache(self.cfg, self.max_slots, self.max_len,
                                   dtype=self.dtype,
                                   paged=(self.num_pages, self.page_size))

    def _cache_pspecs(self):
        # page *contents* shard over kv-heads ("model"); the pages axis
        # itself replicates under the serve tables (any slot must gather
        # any page) and block tables / pos tracks follow "slots"
        return lm.cache_pspecs(self.cfg, self.max_slots, self.max_len,
                               self.mesh, self.rules,
                               paged=(self.num_pages, self.page_size))

    @property
    def stats(self) -> dict:
        """Pool + prefix-sharing counters (see kvpool.PagePool.stats)."""
        return dict(self.pool.stats)

    @property
    def spec_stats(self) -> dict:
        """Speculative-decode counters: per-slot and total drafted/accepted
        tokens.  The acceptance rate is the engine's live analog-fidelity
        signal — how often the low-precision NL-DPE draft agrees with the
        exact digital path (DESIGN.md §8; the paper's Fig 14 correlation,
        observed in production instead of offline).  ``window`` holds the
        same counters since the last :meth:`reset_window`, and
        ``ewma_acceptance`` is a per-tick exponential average — both exist
        because the lifetime totals cannot see a device *degrading*."""
        if not self.spec_k:
            return {"spec_k": 0}
        drafted = int(self._drafted.sum())
        accepted = int(self._accepted.sum())
        wd = int(self._win_drafted.sum())
        wa = int(self._win_accepted.sum())
        return {"spec_k": self.spec_k, "spec_k_live": self.spec_k_live,
                "spec_steps": self._spec_steps,
                "drafted": drafted, "accepted": accepted,
                "acceptance_rate": accepted / max(drafted, 1),
                "ewma_acceptance": self.ewma_acceptance,
                "draft_seconds": self.spec_draft_seconds,
                "drafted_by_slot": self._drafted.tolist(),
                "accepted_by_slot": self._accepted.tolist(),
                "window": {"ticks": self._win_ticks,
                           "drafted": wd, "accepted": wa,
                           "acceptance_rate": wa / max(wd, 1),
                           "drafted_by_slot": self._win_drafted.tolist(),
                           "accepted_by_slot": self._win_accepted.tolist()}}

    def reset_window(self) -> None:
        """Zero the windowed counters in ``spec_stats["window"]`` — a
        dashboard/epoch boundary; lifetime totals and the EWMA keep
        running."""
        if not self.spec_k:
            return
        self._win_drafted[:] = 0
        self._win_accepted[:] = 0
        self._win_ticks = 0

    @property
    def fidelity_stats(self) -> dict:
        """Closed-loop telemetry (DESIGN.md §10): virtual clock, ladder
        state + event log, reprogramming downtime, and the drift plant's
        fault census."""
        out = {"enabled": self.monitor is not None or self.drift is not None,
               "vclock_s": self.vclock,
               "spec_k_live": getattr(self, "spec_k_live", 0),
               "reprograms": self._reprograms,
               "downtime_s": self._downtime_s,
               "disabled_ticks": self._disabled_ticks}
        if self.monitor is not None:
            out.update(ewma=self.monitor.ewma,
                       disabled=self.monitor.disabled,
                       events=list(self.monitor.events),
                       events_dropped=self.monitor.events.dropped)
        if self.drift is not None:
            out["fault_fraction"] = float(drift_lib.fault_fraction(
                self._drift_state, self.vclock))
        return out

    # ------------------------------------------------------------------
    # closed-loop fidelity plumbing (DESIGN.md §10)
    # ------------------------------------------------------------------

    def _spec_fns_for(self, k: int) -> tuple:
        """The (draft, verify) jit pair at live depth ``k`` (cached)."""
        fns = self._spec_fn_cache.get(k)
        if fns is None:
            draft = jax.jit(
                self._ctx(build_draft_scan_fn(
                    self.cfg, spec_k=k, nldpe=self.spec_draft,
                    batch_groups=self.batch_groups)),
                donate_argnums=(1,))    # the cache — never the weights
            verify = jax.jit(
                self._ctx(build_verify_fn(
                    self.cfg, spec_k=k, nldpe=self.nldpe,
                    batch_groups=self.batch_groups, eos_id=self.eos_id)),
                donate_argnums=(1, 2, 3, 4, 5))
            fns = (draft, verify)
            self._spec_fn_cache[k] = fns
        return fns

    def _aged_draft_params(self):
        """The drafter's effective weights *now*: the programmed cells
        drifted to the current virtual time, faulted cells stuck."""
        d = self.drift
        t = jnp.float32(self.vclock)
        if d.read_noise:
            return self._read_fn(self._drift_state, t,
                                 jax.random.fold_in(self._read_key,
                                                    self.tick))
        return self._read_fn(self._drift_state, t)

    def _execute_reprogram(self) -> None:
        """The ladder's recovery action: rewrite every drafter cell through
        a fresh program-and-verify pass at the current virtual time and
        meter the downtime.  Stuck cells survive reprogramming, so each
        recovery peaks slightly lower than the last (the bench sawtooth's
        decaying envelope)."""
        self._reprograms += 1
        if self.drift is None:
            return                      # monitor-only mode: counted, no-op
        self.vclock += self.drift.reprogram_s
        self._downtime_s += self.drift.reprogram_s
        self._drift_key, k = jax.random.split(self._drift_key)
        self._drift_state = self._reprogram_fn(
            k, self._drift_state, self._draft_params,
            jnp.float32(self.vclock))

    def _after_tick(self, *, drafted: int, accepted: int, k: int) -> None:
        """Advance the virtual device clock one tick and run the fidelity
        controller.  Without a drift plant the clock counts exact decode
        positions (1 per spec tick, decode_block per fallback tick)."""
        if self.drift is not None:
            self.vclock += self.drift.tick_seconds(k, self.decode_block)
        else:
            self.vclock += float(self.decode_block if k == 0 else 1)
        if self.monitor is None:
            return
        action = self.monitor.observe(drafted=drafted, accepted=accepted,
                                      t=self.vclock, tick=self.tick)
        if action == "reprogram":
            self._execute_reprogram()
        self.spec_k_live = self.monitor.spec_k
        tel = self.telemetry
        if tel is not None and action is not None:
            tel.event("fidelity", self.tick, kind=action,
                      spec_k=self.monitor.spec_k, ewma=self.monitor.ewma,
                      vclock_s=self.vclock)

    def _dispatch_tick(self):
        """One decode tick's dispatch.  Non-speculative engines scan
        ``decode_block`` plain steps (base class); with ``spec_k`` set, a
        tick is ONE speculative step — k analog drafts + one exact batched
        verify — emitting 1..k+1 tokens per active slot.  Under the
        fidelity loop ``k`` is the monitor's live depth, and ``k == 0``
        (draft disabled) falls back to the base exact scan: the drafter
        never owned correctness, so disabling it moves throughput only.

        Speculative ticks return already-materialized host arrays: draft
        metering and the acceptance counters feeding the fidelity ladder
        need the tick's results on host before the next dispatch, so spec
        serving pipelines admission against decode only."""
        if not self.spec_k:
            return super()._dispatch_tick()
        k = self.spec_k_live = (self.monitor.spec_k
                                if self.monitor is not None else self.spec_k)
        if k == 0:
            out = super()._dispatch_tick()
            self._disabled_ticks += 1
            self._after_tick(drafted=0, accepted=0, k=0)
            return out
        tel = self.telemetry
        if tel is not None:
            tel.tick_boundary(self.tick)
        # explicit copy: np.asarray of a CPU jax array can alias the device
        # buffer, which the verify fn below donates (and so may reuse)
        pre_active = np.array(self._active)
        dparams = (self._aged_draft_params() if self.drift is not None
                   else self._draft_params)
        draft_fn, verify_fn = self._spec_fns_for(k)
        # perf_counter, not time.time(): the wall clock can step backwards
        # under NTP, which produced negative draft phases in long serves
        t0 = time.perf_counter()
        with span(tel, "spec_draft", self.tick, k=k,
                  n_active=int(pre_active.sum())):
            self.cache, drafts, q_probs = draft_fn(
                dparams, self.cache, self._tok, self._pos, self._active,
                self._temp, self._topk, self._keys)
            jax.block_until_ready(drafts)   # meter the analog phase alone
        self.spec_draft_seconds += time.perf_counter() - t0
        # the span closes on np.asarray(accepted), the verify outputs'
        # existing sync
        with span(tel, "spec_verify", self.tick, k=k) as verify:
            (self.cache, self._tok, self._pos, self._active, self._gen_left,
             emits, accepted) = verify_fn(
                self.params, self.cache, self._tok, self._pos, self._active,
                self._gen_left,
                self._temp, self._topk, self._keys, drafts, q_probs)
            self.tick += 1
            self._spec_steps += 1
            drafted_now = np.where(pre_active, k, 0).astype(np.int64)
            accepted_now = np.where(pre_active, np.asarray(accepted),
                                    0).astype(np.int64)
            self._drafted += drafted_now
            self._accepted += accepted_now
            self._win_drafted += drafted_now
            self._win_accepted += accepted_now
            self._win_ticks += 1
            d, a = int(drafted_now.sum()), int(accepted_now.sum())
            verify["drafted"], verify["accepted"] = d, a
        if d:
            acc = a / d
            self.ewma_acceptance = (
                acc if self.ewma_acceptance is None
                else self._ewma_alpha * acc
                + (1 - self._ewma_alpha) * self.ewma_acceptance)
        self._after_tick(drafted=d, accepted=a, k=k)
        # explicit copy again: the next verify donates this active buffer
        return emits_tick_major(emits), np.array(self._active), None

    # ------------------------------------------------------------------
    # jit'd building blocks (paged variants)
    # ------------------------------------------------------------------

    def _build_chunk_fn(self):
        cfg, nldpe, groups = self.cfg, self.nldpe, self.batch_groups
        c = self.prefill_chunk

        def chunk(params, cache, tokens, base_pos, mask, limit):
            """One (max_slots, prefill_chunk) suffix-prefill chunk at
            **per-slot** base positions: prefix hits shift each slot's
            suffix independently, so ``base_pos``/``limit`` are (S,)
            vectors instead of the slotted engine's shared scalars."""
            cache = ServeEngine._clip_pos(cache, mask, base_pos)
            positions = base_pos[:, None] + jnp.arange(c, dtype=jnp.int32)
            logits, cache = lm.forward(params, tokens, cfg, mode="chunk",
                                       cache=cache, positions=positions,
                                       nldpe=nldpe, batch_groups=groups,
                                       write_mask=mask)
            return logits, ServeEngine._clip_pos(cache, mask, limit)

        return chunk

    def _chunk_base(self, reuse, i: int):
        """Per-slot base positions: prefix hits shift each slot's suffix
        independently, so chunk ``i`` starts at ``reuse + i * c`` per slot
        (host or traced arrays alike)."""
        if isinstance(reuse, np.ndarray):
            return jnp.asarray((reuse + i * self.prefill_chunk)
                               .astype(np.int32))
        return (reuse + i * self.prefill_chunk).astype(jnp.int32)

    def _build_setup_fn(self):
        def setup(cache, mask, reuse, new_bt):
            """Admission reset for masked slots, one fused dispatch: the
            block-table row is replaced and the position track becomes
            ``[0, reuse)`` valid (the radix-hit prefix — those pages
            already hold this prompt's K/V), everything else never-valid.
            """
            def one(path, leaf):
                keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
                if not keys or keys[-1] not in ("pos", "bt"):
                    return leaf
                bdim = _batch_dim(path)
                m = _per_slot(mask, leaf, bdim)
                if keys[-1] == "pos":
                    r = _per_slot(reuse, leaf, bdim)
                    iota = jnp.arange(leaf.shape[-1], dtype=jnp.int32)
                    fresh = jnp.where(iota < r, iota, jnp.int32(-1))
                    return jnp.where(m, fresh, leaf)
                nbt = new_bt if leaf.ndim == new_bt.ndim else new_bt[None]
                return jnp.where(m, nbt, leaf)

            return jtu.tree_map_with_path(one, cache)

        return setup

    def _build_copy_fn(self):
        def copy_page(cache, src, dst):
            """Copy-on-write fork: duplicate physical page ``src`` into
            ``dst`` across every layer's K/V (+scale) pool."""
            def one(path, leaf):
                keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
                if not keys or keys[-1] not in ("k", "v", "k_scale",
                                                "v_scale"):
                    return leaf
                ax = _batch_dim(path)              # pages axis of the pool
                row = jax.lax.dynamic_index_in_dim(leaf, src, axis=ax,
                                                   keepdims=True)
                return jax.lax.dynamic_update_index_in_dim(leaf, row, dst,
                                                           axis=ax)

            return jtu.tree_map_with_path(one, cache)

        return copy_page

    def _build_pos_row_fn(self):
        def pos_row(cache, sl):
            """One slot's position-track row.  Every layer's pos leaf is
            written in lockstep (same positions, same masks, same clips),
            so the first leaf is canonical for all of them — that is what
            lets preemption save ONE (max_len,) row and resume rebroadcast
            it to every layer."""
            for path, leaf in jtu.tree_flatten_with_path(cache)[0]:
                keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
                if keys and keys[-1] == "pos":
                    row = jax.lax.dynamic_index_in_dim(
                        leaf, sl, axis=_batch_dim(path), keepdims=False)
                    return row[0] if row.ndim == 2 else row
            raise ValueError("paged cache has no pos leaf")

        return pos_row

    def _build_resume_fn(self):
        def resume(cache, mask, new_bt, pos_row):
            """Resume-time twin of the setup fn: on the masked slot,
            replace the block-table row with the freshly allocated pages
            and set every pos leaf to the preempted request's exact saved
            row (not an iota — the row IS the resume contract: validity
            boundaries land where the last verify clip left them)."""
            def one(path, leaf):
                keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
                if not keys or keys[-1] not in ("pos", "bt"):
                    return leaf
                bdim = _batch_dim(path)
                m = _per_slot(mask, leaf, bdim)
                if keys[-1] == "pos":
                    row = pos_row.astype(leaf.dtype)
                    row = row.reshape((1,) * (leaf.ndim - 1) + row.shape)
                    return jnp.where(m, row, leaf)
                nbt = new_bt if leaf.ndim == new_bt.ndim else new_bt[None]
                return jnp.where(m, nbt, leaf)

            return jtu.tree_map_with_path(one, cache)

        return resume

    # ------------------------------------------------------------------
    # host tier: device→host spill, host→device restore (DESIGN.md §13)
    # ------------------------------------------------------------------

    def _spill_page(self, page: int) -> list:
        """The pool's ``on_spill`` hook: device→host copy of one page's
        bytes across every pool leaf.  ``np.array(..., copy=True)`` is the
        load-bearing part — ``np.asarray`` of a CPU jax array can alias
        device memory that the next donating jit (chunk/decode/verify/
        scatter) reuses, silently corrupting the host copy (the exact trap
        flagged in ROADMAP and fixed in checkpoint/manager.py)."""
        rows = self._gather_fn(self.cache, jnp.int32(page))
        payload = [np.array(r, copy=True) for r in rows]
        tel = self.telemetry
        if tel is not None:
            tel.event("spill", self.tick, page=page)
        return payload

    def _restore_page(self, payload: list, page: int) -> None:
        """Host→device copy: write a spilled payload's rows back as
        physical page ``page`` (one donated jit dispatch)."""
        self.cache = self._scatter_fn(self.cache, list(payload),
                                      jnp.int32(page))

    # ------------------------------------------------------------------
    # admission planning: prefix match -> page budget
    # ------------------------------------------------------------------

    def _plan(self, req: Request, *, peek: bool) -> dict:
        """Map a request onto pages: radix-hit pages to share, an optional
        COW fork, and the fresh pages its prompt+gen footprint needs.

        ``peek=True`` (wave selection) never touches pool state and
        additionally reports ``cost`` — the pages admission would consume:
        fresh allocations plus refcount-0 cache hits, which retaining
        removes from the evictable set.
        """
        ps = self.page_size
        plen = len(req.tokens)
        # two-tier lookup: resident hit pages + the spilled continuation
        # chain.  Non-peek pins the spilled nodes until Phase 1 restores
        # them (or the rollback path unpins).
        hit, spill = self.pool.match_tiers(self._fp, req.tokens, peek=peek)
        fork_src = None
        fork_node = None
        n_hit = len(hit) + len(spill)
        if n_hit and n_hit * ps > plen - 1:
            # cache covers the whole prompt; the boundary page must become
            # private (final-token recompute + decode appends land in it).
            # A spilled boundary is a *payload fork*: its host bytes are
            # injected straight into the private fork page and the node
            # stays spilled for future exact-prefix hits.
            if spill:
                fork_node = spill[-1]
                spill = spill[:-1]
            else:
                fork_src = hit[-1]
                hit = hit[:-1]
            reuse = plen - 1
        else:
            reuse = n_hit * ps
        # page budget includes spec_k positions of slack: every speculative
        # step writes drafted-but-unverified K/V up to spec_k positions past
        # the committed tip, and those writes must land in pages this slot
        # owns (capped at max_len — the pos track drops anything beyond it)
        footprint = min(plen + req.max_new_tokens - 1 + self.spec_k,
                        self.max_len)
        nb_need = -(-footprint // ps)
        # fresh pages cover the host-tier restores, the fork, and the
        # suffix — only resident hits come for free
        n_fresh = nb_need - len(hit)
        plan = {"hit": hit, "spill": spill, "fork_src": fork_src,
                "fork_node": fork_node, "reuse": reuse,
                "nb_need": nb_need, "n_fresh": n_fresh}
        if peek:
            ref0 = [p for p in hit if self.pool.refcount(p) == 0]
            plan["ref0_pages"] = ref0
            plan["cost"] = n_fresh + len(ref0)
        return plan

    def _select_wave(self, waiting: deque) -> list[Request]:
        """Admit requests while both a slot and their page budget fit.
        Leaves the rest queued until completions release pages; raises if
        the head request cannot fit even into an idle pool (it never
        will).

        Priority preemption: when the (priority-ordered) head does not
        fit, a strictly-lower-priority running slot may be swapped out to
        host to make room — but only while the wave is still empty, so
        every committed peek plan postdates every preemption this call
        makes (a victim's released pages change the ref-0/hit picture,
        which would silently stale earlier plans)."""
        self._priority_order(waiting)
        wave: list[Request] = []
        spent = 0
        charged: set[int] = set()       # ref-0 hit pages already budgeted —
        while waiting:
            if len(wave) >= len(self._free):
                if wave or not self._preempt_for(waiting[0]):
                    break
                continue                # a slot freed; re-check the head
            plan = self._plan(waiting[0], peek=True)
            # — wave-mates sharing a cached prefix retain the same physical
            # pages, so each one leaves the evictable set exactly once
            ref0_new = [p for p in plan["ref0_pages"] if p not in charged]
            cost = plan["n_fresh"] + len(ref0_new)
            if cost > self.pool.available() - spent:
                if wave or not self._preempt_for(waiting[0]):
                    break
                continue                # pages freed; replan the head
            charged.update(ref0_new)
            spent += cost
            wave.append(waiting.popleft())
        if not wave and waiting and not self.any_active:
            need = self._plan(waiting[0], peek=True)["cost"]
            raise RuntimeError(
                f"request {waiting[0].rid} needs {need} pages but the pool "
                f"holds {self.pool.num_pages} (page_size="
                f"{self.page_size}); grow num_pages or shrink the request")
        return wave

    # ------------------------------------------------------------------
    # priority preemption: swap a running slot out to host, resume later
    # ------------------------------------------------------------------

    def _can_admit(self, waiting: deque) -> bool:
        """A full engine can still admit when some waiting request
        strictly outranks a running slot — ``_select_wave`` will preempt
        the victim to make room."""
        if self._free:
            return True
        top = max(r.priority for r in waiting)
        return any(r is not None and r.priority < top
                   for r in self._slot_owner)

    def _preempt_for(self, incoming: Request) -> bool:
        """Swap out one running victim for a strictly-higher-priority
        incoming request.  Victim order is total and deterministic —
        lowest priority, then most recently admitted, then highest rid —
        so scheduling (and every downstream token) is reproducible."""
        victims = [(r.priority, -self._admitted_tick[r.rid], -r.rid, sl)
                   for sl, r in enumerate(self._slot_owner)
                   if r is not None and r.priority < incoming.priority]
        if not victims:
            return False
        self._preempt_slot(min(victims)[3])
        return True

    def _preempt_slot(self, sl: int) -> None:
        """Swap slot ``sl`` out to host RAM: copy its decode-state row,
        sampling key, canonical pos row, and every block-table page's
        bytes (the spill gather path), then release the pages WITHOUT
        publish — mid-flight K/V past the committed prefix must never
        enter the radix index.  The payloads are engine-held and do not
        consume the pool's ``host_pages`` budget (preemption must work
        even with the spill tier off)."""
        req = self._slot_owner[sl]
        assert req is not None, "preempt of an empty slot"
        # explicit copies: the decode/verify jits donate all of these
        tok = np.array(self._tok)
        pos = np.array(self._pos)
        gen = np.array(self._gen_left)
        temp = np.array(self._temp)
        topk = np.array(self._topk)
        keys = np.array(self._keys)
        pos_row = np.array(self._pos_row_fn(self.cache, jnp.int32(sl)),
                           copy=True)
        pages = self._slot_pages[sl]
        payloads = [[np.array(r, copy=True)
                     for r in self._gather_fn(self.cache, jnp.int32(p))]
                    for p in pages]
        tel = self.telemetry
        carry = (0, 0)
        if tel is not None and req.rid in self._tel_admit:
            _, d0, a0 = self._tel_admit[req.rid]
            if self.spec_k:
                carry = (int(self._drafted[sl]) - d0,
                         int(self._accepted[sl]) - a0)
        self.pool.release(pages)
        self._slot_pages[sl] = None
        self._slot_owner[sl] = None
        self._free.append(sl)
        # clear the device active bit so the shared decode scan freezes
        # this row (its block table still maps the released pages)
        mask = np.zeros((self.max_slots,), bool)
        mask[sl] = True
        self._active = self._deact_fn(self._active, jnp.asarray(mask))
        self._preempted.append(_Preempted(
            req=req, tok=int(tok[sl]), pos=int(pos[sl]),
            gen_left=int(gen[sl]), temp=float(temp[sl]),
            topk=int(topk[sl]), keys=keys[sl].copy(),
            pos_row=pos_row, payloads=payloads, tel_carry=carry))
        self.preempts += 1
        if tel is not None:
            tel.event("preempt", self.tick, rid=req.rid, slot=sl,
                      pages=len(payloads), priority=req.priority)

    def _resume_preempted(self, waiting=()) -> None:
        """Swap preempted requests back in: highest priority first (FIFO
        within a class).  A strictly-higher-priority *waiting* request
        holds resumes back — admission would only preempt the resumee
        again, wasting two page-image round trips."""
        if not self._preempted:
            return
        self._preempted.sort(key=lambda p: -p.req.priority)
        top_wait = max((r.priority for r in waiting), default=None)
        kept: list[_Preempted] = []
        for pre in self._preempted:
            if (self._free
                    and (top_wait is None
                         or pre.req.priority >= top_wait)
                    and self._resume_one(pre)):
                continue
            kept.append(pre)
        self._preempted = kept

    def _resume_one(self, pre: _Preempted) -> bool:
        """Restore one preempted request into a free slot: allocate its
        page count, inject every payload, rewrite the slot's bt row and
        pos track, and merge its decode-state row back — after which the
        request is indistinguishable from one that was never preempted."""
        n = len(pre.payloads)
        fresh = self.pool.alloc(n)
        if fresh is None:
            return False
        sl = self._free.popleft()
        for payload, pg in zip(pre.payloads, fresh):
            self._restore_page(payload, pg)
        s = self.max_slots
        mask = np.zeros((s,), bool)
        mask[sl] = True
        new_bt = np.full((s, self.n_blocks), self.num_pages, np.int32)
        new_bt[sl, :n] = fresh
        self.cache = self._resume_fn(self.cache, jnp.asarray(mask),
                                     jnp.asarray(new_bt),
                                     jnp.asarray(pre.pos_row))
        n_tok = np.zeros((s,), np.int32)
        n_pos = np.zeros((s,), np.int32)
        n_gen = np.zeros((s,), np.int32)
        n_temp = np.zeros((s,), np.float32)
        n_topk = np.zeros((s,), np.int32)
        n_keys = np.zeros((s, 2), np.uint32)
        n_tok[sl] = pre.tok
        n_pos[sl] = pre.pos
        n_gen[sl] = pre.gen_left
        n_temp[sl] = pre.temp
        n_topk[sl] = pre.topk
        n_keys[sl] = pre.keys
        (self._tok, self._pos, self._active, self._gen_left, self._temp,
         self._topk, self._keys) = self._state_fn(
            self._tok, self._pos, self._active, self._gen_left,
            self._temp, self._topk, self._keys, jnp.asarray(mask),
            jnp.asarray(n_tok), jnp.asarray(n_pos), jnp.asarray(n_gen),
            jnp.asarray(n_temp), jnp.asarray(n_topk), jnp.asarray(n_keys))
        self._slot_pages[sl] = list(fresh)
        self._slot_owner[sl] = pre.req
        self.resumes += 1
        tel = self.telemetry
        if tel is not None:
            # re-seed the per-request spec attribution baseline so finish
            # still reports drafted/accepted as if never preempted
            base_d = base_a = 0
            if self.spec_k:
                base_d = int(self._drafted[sl]) - pre.tel_carry[0]
                base_a = int(self._accepted[sl]) - pre.tel_carry[1]
            self._tel_admit[pre.req.rid] = (sl, base_d, base_a)
            tel.event("resume", self.tick, rid=pre.req.rid, slot=sl,
                      pages=n)
        return True

    def _release_slot(self, sl: int, seq: tuple | None = None) -> None:
        pages = self._slot_pages[sl]
        if pages is not None:
            if seq is not None and self.cache_generations:
                # publish the request's *committed* sequence — prompt plus
                # verified generations — so future prompts sharing it hit
                # the cache.  publish_committed only admits pages whose
                # every position is committed: drafted-but-rejected tokens
                # and the spec page slack can never enter the radix index
                # (the provisional-length protocol, DESIGN.md §8)
                self.pool.publish_committed(self._fp, seq, pages)
            self.pool.release(pages)
            self._slot_pages[sl] = None

    # ------------------------------------------------------------------
    # admission: plan -> retain/alloc -> publish -> COW -> suffix prefill
    # ------------------------------------------------------------------

    def _admit_wave(self, reqs: list[Request]) -> list[Completion]:
        self._check_wave(reqs)
        tel = self.telemetry
        s, c = self.max_slots, self.prefill_chunk
        with span(tel, "admission_wave", self.tick,
                  rids=[r.rid for r in reqs], n_reqs=len(reqs)) as wave:
            with span(tel, "plan", self.tick):
                slots, plans, admit, reuse_np, plen_np = self._plan_wave(
                    reqs)
            # Phase 4 — chunked SUFFIX prefill at per-slot base positions.
            with span(tel, "prefill", self.tick):
                suffix = plen_np - reuse_np        # >= 1: last token always
                n_chunks = -(-int(suffix[admit].max()) // c)
                tokens = np.zeros((s, n_chunks * c), np.int32)
                ci_np = np.zeros((s,), np.int32)
                col_np = np.zeros((s,), np.int32)
                keys_np = np.zeros((s, 2), np.uint32)
                temp_np = np.zeros((s,), np.float32)
                topk_np = np.zeros((s,), np.int32)
                for r, sl, plan in zip(reqs, slots, plans):
                    tokens[sl, :suffix[sl]] = r.tokens[plan["reuse"]:]
                    ci_np[sl] = (suffix[sl] - 1) // c
                    col_np[sl] = (suffix[sl] - 1) % c
                    keys_np[sl] = np.asarray(
                        request_key(r.seed if r.seed is not None else r.rid))
                    temp_np[sl] = r.temperature
                    topk_np[sl] = r.top_k
                last, wave["n_chunks"] = self._prefill_chunks(
                    admit, plen_np, reuse_np, tokens, ci_np, col_np)
            # Phase 5 — the slotted engine's post-prefill bookkeeping
            return self._seat(reqs, slots, last, keys_np, plen_np, temp_np,
                              topk_np, [(p["reuse"], p["nb_need"])
                                        for p in plans])

    def _plan_wave(self, reqs: list[Request]):
        """Phases 1-3 of an admission wave, all host work and page copies:
        take slots, plan and commit pages, publish, reset block tables.
        Returns (slots, plans, admit, reuse, plen)."""
        tel = self.telemetry
        s, ps = self.max_slots, self.page_size

        # Phase 1 — plan + commit pool state for every request BEFORE any
        # publish: requests in one wave never share each other's pages
        # (their prefill runs in the same chunk dispatches, so one slot's
        # pages are not fully written when another's queries would attend).
        slots = [self._free.popleft() for _ in reqs]
        plans = []
        for r, sl in zip(reqs, slots):
            plan = self._plan(r, peek=False)
            self.pool.retain(plan["hit"])
            fresh = self.pool.alloc(plan["n_fresh"])
            if fresh is None:                      # submit() without budget
                self.pool.release(plan["hit"])
                self.pool.unpin(plan["spill"])
                if plan["fork_node"] is not None:
                    self.pool.unpin([plan["fork_node"]])
                for pl in plans:                   # roll back committed reqs
                    self.pool.release(pl["hit"])
                    self.pool.release(pl["fresh"])
                    # pl["spill"] nodes were already restored (now ordinary
                    # resident cache — correct bytes, no pin); only a
                    # pending payload fork still holds a pin
                    if pl["fork_node"] is not None:
                        self.pool.unpin([pl["fork_node"]])
                for sl2 in reversed(slots):
                    self._free.appendleft(sl2)
                raise RuntimeError(
                    f"request {r.rid}: page pool exhausted "
                    f"({self.pool.available()} available, "
                    f"{plan['n_fresh']} needed); check free pages before "
                    f"submit or let run() schedule admission")
            plan["fresh"] = fresh
            # restore the spilled chain NOW, top-down, into the leading
            # fresh pages — before the next request plans, so wave-mates
            # sharing the chain see ordinary resident hits (and never
            # double-restore), and before publish (restore-before-publish)
            for nd, pg in zip(plan["spill"], fresh):
                self._restore_page(nd.payload, pg)
                self.pool.restore(nd, pg)
                if tel is not None:
                    tel.event("restore", self.tick, page=pg)
            plans.append(plan)

        # Every allocation succeeded — only now dispatch COW page copies
        # and bump pool stats, so a failed wave leaves the device cache
        # and the prefix-savings counters untouched.
        for r, sl, plan in zip(reqs, slots, plans):
            fresh = plan["fresh"]
            # leading fresh pages took the Phase-1 restores; the rest
            # carry the fork (if any) and the suffix
            restored = fresh[:len(plan["spill"])]
            rest = fresh[len(plan["spill"]):]
            if plan["fork_node"] is not None:
                # payload fork: the boundary chunk lives host-side.  If a
                # wave-mate restored the node in Phase 1 it is resident
                # again — fall back to an ordinary device-side COW copy
                # (the bytes are identical either way).
                nd = plan["fork_node"]
                fork_dst = rest[0]
                if nd.page >= 0:
                    self.cache = self._copy_fn(self.cache,
                                               jnp.int32(nd.page),
                                               jnp.int32(fork_dst))
                else:
                    self._restore_page(nd.payload, fork_dst)
                self.pool.unpin([nd])
                self.pool.note_cow()
                if tel is not None:
                    tel.event("cow_fork", self.tick,
                              src=nd.page if nd.page >= 0 else -1,
                              dst=fork_dst)
                bt_row = plan["hit"] + restored + [fork_dst] + rest[1:]
            elif plan["fork_src"] is not None:
                fork_dst = rest[0]
                self.cache = self._copy_fn(self.cache,
                                           jnp.int32(plan["fork_src"]),
                                           jnp.int32(fork_dst))
                self.pool.note_cow()
                if tel is not None:
                    tel.event("cow_fork", self.tick,
                              src=plan["fork_src"], dst=fork_dst)
                bt_row = plan["hit"] + restored + [fork_dst] + rest[1:]
            else:
                bt_row = plan["hit"] + restored + rest
            assert len(bt_row) == plan["nb_need"]
            plan["bt_row"] = bt_row
            self._slot_pages[sl] = list(bt_row)
            self.pool.stats["prefill_tokens_saved"] += plan["reuse"]

        # Phase 2 — publish full prompt pages for *future* waves (walk
        # skips chunks already in the index, so hit/forked pages whose
        # chunk is published stay private duplicates).
        for r, plan in zip(reqs, plans):
            n_full = len(r.tokens) // ps
            self.pool.publish(self._fp, r.tokens, plan["bt_row"][:n_full])

        # Phase 3 — one fused jit reset: block tables + pos tracks (the
        # radix-hit prefix [0, reuse) is immediately valid).
        admit = np.zeros((s,), bool)
        reuse_np = np.zeros((s,), np.int32)
        # unallocated blocks keep the out-of-range sentinel: padded chunk
        # tails that reach past nb_need must drop, not hit page 0
        new_bt = np.full((s, self.n_blocks), self.num_pages, np.int32)
        plen_np = np.ones((s,), np.int32)
        for r, sl, plan in zip(reqs, slots, plans):
            admit[sl] = True
            reuse_np[sl] = plan["reuse"]
            new_bt[sl, :plan["nb_need"]] = plan["bt_row"]
            plen_np[sl] = len(r.tokens)
        self.cache = self._setup_fn(self.cache, jnp.asarray(admit),
                                    jnp.asarray(reuse_np),
                                    jnp.asarray(new_bt))
        return slots, plans, admit, reuse_np, plen_np
