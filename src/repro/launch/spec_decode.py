"""Analog-draft speculative decoding for the paged serve engine.

NL-DPE's core trade is a cheap-but-noisy analog path against an exact
digital one.  That trade is exactly the draft/verify split of speculative
decoding, so this module lets the two paths cooperate inside a single
decode step instead of being alternates (DESIGN.md §8):

* **Draft** — ``spec_k`` sequential decode steps through the NL-DPE
  low-precision path: the drafter's weights are the model's own parameters
  round-tripped through the 8-bit log-quant ACAM grid
  (``quantize_draft_params`` — the conductances the crossbars would hold;
  no second model to train or store), optionally with the full analog
  numerics (log-domain DMMul, ACAM softmax) on activations too.  Draft K/V
  land *provisionally* in the slot's own pages at positions
  ``[pos, pos+k)`` — the engine allocates ``spec_k`` positions of page
  slack per request so these writes never spill into another slot's pages.
* **Verify** — ONE exact-digital ``mode="chunk"`` forward scores all
  ``k+1`` positions at once against the paged KV cache: the chunk first
  overwrites positions ``[pos, pos+k]`` with exact K/V (burying every
  draft write), then each query ``j`` attends to cache lines at positions
  ``<= pos+j`` under the standard validity mask — bit-identical, position
  for position, to ``k+1`` sequential decode steps (asserted in
  tests/test_engine_differential.py).
* **Accept / rollback** — standard speculative rejection sampling
  (``speculative_accept``): greedy requests accept a draft iff it equals
  the verify argmax, so greedy outputs are bit-exact with non-speculative
  decode; sampled requests accept ``d ~ q`` with probability
  ``min(1, p[d]/q[d])`` and draw rejections from the leftover
  distribution ``residual_probs(p, q)``, which preserves the target
  distribution exactly.  All speculative randomness folds the *verified
  token position* (``sampling.spec_fold`` streams), so outputs stay
  trace- and placement-invariant.  After acceptance, position-track
  entries at and beyond the new sequence tip are clipped back to
  never-valid: rejected draft/verify writes become dead bytes in pages the
  slot still owns — they are re-written by the next verify chunk before
  they can ever become valid, and the engine publishes only *committed*
  positions to the radix index (``kvpool.publish_committed``).

Per spec step a slot emits between 1 (draft rejected immediately: the
correction token) and ``k+1`` (all drafts accepted + the bonus token)
tokens; the acceptance rate is the analog-fidelity signal — the software
mirror of the paper's Fig 14 device-noise correlation.

Mesh-sharded serving (DESIGN.md §9): the engine traces the draft scan and
the verify pass under its sharding context, so both phases shard exactly
like plain decode — drafter weights follow the target params' placement
(``PagedServeEngine`` quantizes the *placed* params), heads over "model",
slots over "data".  Under the exact rule tables the draft tokens, accept
draws, and rollback clips are all bit-identical to single-device, which
is why the sharded differential matrix can assert acceptance-counter
equality, not just token equality.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from ..core.engine import NLDPEConfig, OFF
from ..core.logdomain import LogDomainConfig, log_quantize
from ..models import lm
from .sampling import (ACCEPT_STREAM, CORRECT_STREAM, DRAFT_STREAM,
                       residual_probs, sample_from_probs, spec_fold,
                       target_probs)


# ---------------------------------------------------------------------------
# cache-tree helpers (shared with launch/engine.py)
# ---------------------------------------------------------------------------

def pos_leaf(path) -> bool:
    keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
    return bool(keys) and keys[-1] == "pos"


def batch_dim(path) -> int:
    """Cache leaves under "groups" are stacked (n_groups, B, ...); "tail"
    leaves are (B, ...)."""
    keys = [k.key for k in path if isinstance(k, jtu.DictKey)]
    return 1 if keys and keys[0] == "groups" else 0


def per_slot(a: jax.Array, leaf: jax.Array, bdim: int) -> jax.Array:
    """Broadcast a per-slot vector (S,) against a cache leaf along bdim."""
    shape = [1] * leaf.ndim
    shape[bdim] = a.shape[0]
    return a.reshape(shape)


def clip_positions(cache, mask, bound):
    """On masked slots, make every cache line at position >= bound
    never-valid (pos <- -1).  bound is () or (S,).  This is both the
    admission reset of the serve engines and the speculative *rollback*:
    after acceptance, entries past the new tip are unverified draft state
    and must never be attended."""
    bound = jnp.asarray(bound, jnp.int32)

    def one(path, leaf):
        if not pos_leaf(path):
            return leaf
        bdim = batch_dim(path)
        m = per_slot(mask, leaf, bdim)
        b = per_slot(bound, leaf, bdim) if bound.ndim else bound
        return jnp.where(m & (leaf >= b), jnp.int32(-1), leaf)

    return jtu.tree_map_with_path(one, cache)


def emits_tick_major(emits) -> np.ndarray:
    """Materialize a verify step's per-slot emissions (S, k+1) into the
    tick-major (T, S) host layout the engine harvest consumes (the plain
    decode scan already emits tick-major).  One named place pins this
    layout contract now that two consumers exist: the synchronous
    ``step()`` and the async engine's drain path."""
    return np.asarray(emits).T


# ---------------------------------------------------------------------------
# drafter weights: parameters as programmed conductances
# ---------------------------------------------------------------------------

def quantize_draft_params(params, logdomain: LogDomainConfig | None = None):
    """Round-trip every parameter through the 8-bit sign-magnitude log
    grid (``core.logdomain.log_quantize``) — the values the crossbar cells
    would actually hold once programmed.  Computed once at engine init and
    cached on device; the drafter then runs the *same* forward as the
    target, just with conductance-faithful weights (plus whatever analog
    numerics its NLDPEConfig enables).  The copy keeps each parameter's
    dtype, so bf16 serving holds two bf16 weight sets, not bf16 + f32."""
    if logdomain is None:
        logdomain = LogDomainConfig()
    # one fused program per leaf: eager ops would hold several f32
    # temporaries of the largest weight stack at once
    program = jax.jit(lambda w: log_quantize(w.astype(jnp.float32),
                                             logdomain).astype(w.dtype))
    return jax.tree.map(program, params)


# ---------------------------------------------------------------------------
# rejection sampling
# ---------------------------------------------------------------------------

def speculative_accept(drafts, q_probs, vlogits, temperature, top_k, keys,
                       pos):
    """Vectorized accept/reject + correction over one spec step.

    drafts (S, k) int32 draft tokens; q_probs (S, k, V) the draft
    distributions they were sampled from; vlogits (S, k+1, V) exact verify
    logits (index j scored with context through position pos+j);
    temperature/top_k (S,); keys (S, 2); pos (S,) current positions.

    Returns (accepted (S,) in [0, k], correction (S,) int32) where
    ``correction`` is the token to emit at index ``accepted``: the
    residual-distribution draw at a rejection, or the bonus sample from
    the last verify distribution when every draft was accepted.  Greedy
    slots (temperature <= 0) reduce to one-hot p/q, making acceptance
    ``draft == argmax`` and the correction the verify argmax — bit-exact
    greedy, with the keys consumed but never affecting the outcome.
    """
    s, k, v = q_probs.shape
    temp_r = jnp.repeat(temperature, k + 1)
    topk_r = jnp.repeat(top_k, k + 1)
    p_all = target_probs(vlogits.reshape(s * (k + 1), v), temp_r,
                         topk_r).reshape(s, k + 1, v)

    # accept d_j+1 with prob min(1, p[d]/q[d]); u*q < p avoids the divide
    p_d = jnp.take_along_axis(p_all[:, :k], drafts[..., None], -1)[..., 0]
    q_d = jnp.take_along_axis(q_probs, drafts[..., None], -1)[..., 0]
    jpos = pos[:, None] + 1 + jnp.arange(k, dtype=jnp.int32)
    akeys = spec_fold(keys, jpos, ACCEPT_STREAM)                  # (S, k, 2)
    u = jax.vmap(jax.vmap(jax.random.uniform))(akeys)             # (S, k)
    accept = u * q_d < p_d
    acc_run = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    accepted = jnp.sum(acc_run, axis=1)                           # (S,)

    # correction candidates at every index, gathered at the reject point:
    # residual for j < k, the plain target (bonus) at j == k
    res = residual_probs(p_all[:, :k].reshape(s * k, v),
                         q_probs.reshape(s * k, v)).reshape(s, k, v)
    cand = jnp.concatenate([res, p_all[:, k:]], axis=1)           # (S,k+1,V)
    cpos = pos[:, None] + 1 + jnp.arange(k + 1, dtype=jnp.int32)
    ckeys = spec_fold(keys, cpos, CORRECT_STREAM)                 # (S,k+1,2)
    corr_all = sample_from_probs(ckeys.reshape(s * (k + 1), 2),
                                 cand.reshape(s * (k + 1), v))
    corr_all = corr_all.reshape(s, k + 1)
    correction = jnp.take_along_axis(corr_all, accepted[:, None], 1)[:, 0]
    return accepted, correction


# ---------------------------------------------------------------------------
# the fused spec step
# ---------------------------------------------------------------------------

def build_draft_scan_fn(cfg, *, spec_k: int,
                        nldpe: NLDPEConfig, batch_groups: int = 1):
    """The draft phase alone: spec_k sequential low-precision decode steps
    against the (paged) cache.  The engine dispatches this as its own jit
    (the analog engine's half of a spec step) and meters its wall share —
    the part a real NL-DPE chip would execute in analog; the CPU host pays
    full simulation cost for it (DESIGN.md §8).

    ``draft_params`` is a *call-time* argument (not closed over): under
    drift injection (core/drift.py, DESIGN.md §10) the drafter's effective
    weights change every tick as the programmed conductances age, so the
    engine re-reads them from the device state and passes the result in —
    same shapes every call, so the jit never retraces."""

    def draft_scan(draft_params, cache, tok, pos, active, temp, topk, keys):
        def dstep(carry, _):
            cache, t, p = carry
            logits, cache = lm.decode_step(draft_params, cfg, t, p, cache,
                                           nldpe=nldpe,
                                           batch_groups=batch_groups,
                                           write_mask=active)
            q = target_probs(logits, temp, topk)
            dkeys = spec_fold(keys, p + 1, DRAFT_STREAM)
            d = sample_from_probs(dkeys, q)
            return (cache, d, p + 1), (d, q)

        (cache, _, _), (drafts, q_probs) = jax.lax.scan(
            dstep, (cache, tok, pos), None, length=spec_k)
        return cache, drafts.T, jnp.moveaxis(q_probs, 0, 1)   # (S,k), (S,k,V)

    return draft_scan


def build_verify_fn(cfg, *, spec_k: int, nldpe: NLDPEConfig = OFF,
                    batch_groups: int = 1, eos_id: int = -1):
    """The digital half of one speculative step, one jit:

    exact verify chunk -> rejection sampling -> state update (eos /
    gen-budget truncation, position advance, rollback clip).

    The engine dispatches the draft scan and this verify pass as two jits
    per step — they are two different hardware units (analog engine vs
    digital verifier), and keeping the boundary lets the engine meter the
    analog phase's wall share exactly (``PagedServeEngine.spec_stats``,
    the basis of the bench's analog-cost-modeled row, DESIGN.md §8).

    Returns ``(cache, tok, pos, active, gen_left, emits, accepted)`` with
    ``emits`` (S, k+1) int32, -1 padded past each slot's emitted count
    (chronological per row), and ``accepted`` (S,) the verification
    acceptance count (before eos/budget truncation — the fidelity signal).
    """
    k = spec_k

    def verify_step(params, cache, tok, pos, active, gen_left, temp, topk,
                    keys, drafts, q_probs):
        s = tok.shape[0]
        # exact verify: one chunk over [tok, d_1..d_k] at [pos, pos+k] —
        # overwrites every provisional draft write with exact-digital K/V
        x = jnp.concatenate([tok[:, None], drafts], axis=1)
        positions = pos[:, None] + jnp.arange(k + 1, dtype=jnp.int32)
        vlogits, cache = lm.forward(params, x, cfg, mode="chunk", cache=cache,
                                    positions=positions, nldpe=nldpe,
                                    batch_groups=batch_groups,
                                    write_mask=active)
        accepted, correction = speculative_accept(
            drafts, q_probs, vlogits, temp, topk, keys, pos)

        # emits: drafts below the reject point, the correction at it,
        # then truncation by generation budget and eos
        idx = jnp.arange(k + 1, dtype=jnp.int32)[None]
        d_pad = jnp.concatenate(
            [drafts, jnp.zeros((s, 1), jnp.int32)], axis=1)
        emit = jnp.where(idx < accepted[:, None], d_pad, -1)
        emit = jnp.where(idx == accepted[:, None], correction[:, None], emit)
        emit = jnp.where(idx < gen_left[:, None], emit, -1)
        if eos_id >= 0:
            is_eos = (emit == eos_id).astype(jnp.int32)
            emit = jnp.where(jnp.cumsum(is_eos, axis=1) - is_eos > 0, -1,
                             emit)
        emit = jnp.where(active[:, None], emit, -1)
        n_emit = jnp.sum((emit >= 0).astype(jnp.int32), axis=1)

        # rollback: everything at/after the new tip is unverified state
        cache = clip_positions(cache, active, pos + n_emit)

        last = jnp.take_along_axis(
            emit, jnp.maximum(n_emit - 1, 0)[:, None], 1)[:, 0]
        tok = jnp.where(active & (n_emit > 0), last, tok)
        pos = pos + n_emit
        gen_left = gen_left - n_emit
        done = gen_left <= 0
        if eos_id >= 0:
            done = done | jnp.any(emit == eos_id, axis=1)
        active = active & ~done
        accepted = jnp.where(n_emit > 0, accepted, 0)
        return cache, tok, pos, active, gen_left, emit, accepted

    return verify_step
