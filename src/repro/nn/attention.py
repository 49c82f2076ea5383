"""Attention: GQA/MQA, causal/prefix/sliding-window, KV cache, NL-DPE mode.

Three compute paths chosen by shape/mode:

* ``blockwise`` — training & prefill: pure-JAX flash (online softmax over KV
  blocks, scan over Q blocks) so 32k-token scores never materialize.  This
  is the lax twin of kernels/flash_attention (which is the TPU Pallas path,
  validated in interpret mode; the lax version is what the CPU dry-run
  lowers).
* ``banded``   — sliding-window layers (gemma3 local, recurrentgemma):
  per-Q-block dynamic slice of the KV band -> O(S * window) compute.
* ``decode``   — single-token step against a (possibly ring-buffered) cache;
  paged caches on a TPU read their live pages through
  kernels/paged_attention instead (``paged_kernel_path``).

GQA is computed grouped ('bkgqd,bkld->bkgql'), never materializing repeated
KV heads.  NL-DPE numerics route through core.attention.nldpe_attention
(log-domain DMMuls + ACAM softmax) when enabled.
"""
from __future__ import annotations

import dataclasses
import math
import os

import jax
import jax.numpy as jnp

from ..core.engine import NLDPEConfig, OFF
from ..core.quantization import KV_LOG_SPEC, kv_decode
from ..parallel.context import shard
from .basic import apply_rope, linear_apply, param, rmsnorm_apply, rmsnorm_init
from .module import param as _param

NEG_INF = float(jnp.finfo(jnp.float32).min)


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: int | None = None          # sliding-window size (None = global)
    qk_norm: bool = False              # gemma3-style per-head RMS on q/k
    softcap: float | None = None
    kv_quant: str | None = None        # KV cache storage grid: "int8"/"log8"

    @property
    def group(self) -> int:
        return self.n_heads // self.n_kv_heads


def attn_init(key, s: AttnSpec):
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    p = {
        "wq": _param(kq, (s.d_model, s.n_heads, s.head_dim),
                     ("embed", "heads", None)),
        "wk": _param(kk, (s.d_model, s.n_kv_heads, s.head_dim),
                     ("embed", "kv_heads", None)),
        "wv": _param(kv, (s.d_model, s.n_kv_heads, s.head_dim),
                     ("embed", "kv_heads", None)),
        # "o_heads", not "heads": this is the output projection's
        # *contraction* dim — rule tables that must stay bit-exact under
        # sharding replicate it (parallel/sharding.INEXACT_AXES)
        "wo": _param(ko, (s.n_heads, s.head_dim, s.d_model),
                     ("o_heads", None, "embed"),
                     scale=(s.n_heads * s.head_dim) ** -0.5),
    }
    if s.qkv_bias:
        p["bq"] = _param(key, (s.n_heads, s.head_dim), ("heads", None), init="zeros")
        p["bk"] = _param(key, (s.n_kv_heads, s.head_dim), ("kv_heads", None), init="zeros")
        p["bv"] = _param(key, (s.n_kv_heads, s.head_dim), ("kv_heads", None), init="zeros")
    if s.qk_norm:
        p["q_norm"] = rmsnorm_init(kn, s.head_dim)
        p["k_norm"] = rmsnorm_init(kn, s.head_dim)
    return p


def _project_qkv(p, s: AttnSpec, x: jax.Array, positions: jax.Array):
    """x: (B, S, d) -> q (B, Hq, S, Dh), k/v (B, Hkv, S, Dh), rope applied."""
    q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)[None, :, None, :]
        k = k + p["bk"].astype(x.dtype)[None, :, None, :]
        v = v + p["bv"].astype(x.dtype)[None, :, None, :]
    if "q_norm" in p:
        q = rmsnorm_apply(p["q_norm"], q)
        k = rmsnorm_apply(p["k_norm"], k)
    q = apply_rope(q, positions, s.rope_theta)
    k = apply_rope(k, positions, s.rope_theta)
    q = shard(q, "batch", "heads", None, None)
    k = shard(k, "batch", "kv_heads", None, None)
    v = shard(v, "batch", "kv_heads", None, None)
    return q, k, v


def _mask(q_pos, k_pos, *, causal: bool, window: int | None,
          prefix_len: jax.Array | None):
    """q_pos (..., Q), k_pos (..., K) -> bool (..., Q, K)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    m = (qp >= kp) if causal else jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
    if window is not None:
        m = m & (qp - kp < window)
    if prefix_len is not None:
        m = m | (kp < prefix_len)
    return m


def _sdpa(q, k, v, mask, softcap=None):
    """Grouped GQA attention with materialized scores (small extents only).

    q: (B, Hkv, G, Q, D); k/v: (B, Hkv, K, D); mask broadcastable (B,1,1,Q,K).
    """
    d = q.shape[-1]
    s = jnp.einsum("bkgqd,bkld->bkgql", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(d)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgql,bkld->bkgqd", p, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# blockwise flash (train / prefill)
# ---------------------------------------------------------------------------

def blockwise_attention(q, k, v, *, causal=True, window=None, prefix_len=None,
                        softcap=None, q_block=512, k_block=1024):
    """q: (B,Hq,S,D), k/v: (B,Hkv,S,D) -> (B,Hq,S,D).  Online softmax."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    qb = min(q_block, sq)
    while sq % qb:
        qb //= 2
    kb = min(k_block, sk)
    while sk % kb:
        kb //= 2
    nq, nk = sq // qb, sk // kb
    qg = q.reshape(b, hkv, g, nq, qb, d).astype(jnp.float32) / math.sqrt(d)
    kg = k.reshape(b, hkv, nk, kb, d).astype(jnp.float32)
    vg = v.reshape(b, hkv, nk, kb, d).astype(jnp.float32)

    def q_step(iq):
        q_i = qg[:, :, :, iq]                               # (B,Hkv,G,qb,D)
        q_pos = iq * qb + jnp.arange(qb)

        def kv_step(carry, ik):
            m_run, l_run, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(kg, ik, axis=2, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vg, ik, axis=2, keepdims=False)
            s = jnp.einsum("bkgqd,bkld->bkgql", q_i, k_j)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            k_pos = ik * kb + jnp.arange(kb)
            msk = _mask(q_pos, k_pos, causal=causal, window=window,
                        prefix_len=prefix_len)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            pj = jnp.exp(s - m_safe[..., None])
            corr = jnp.where(jnp.isfinite(m_run), jnp.exp(m_run - m_safe), 0.0)
            l_new = l_run * corr + jnp.sum(pj, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum("bkgql,bkld->bkgqd", pj, v_j)
            return (m_new, l_new, acc), None

        init = (jnp.full((b, hkv, g, qb), NEG_INF, jnp.float32),
                jnp.zeros((b, hkv, g, qb), jnp.float32),
                jnp.zeros((b, hkv, g, qb, d), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(nk))
        l_f = jnp.where(l_f == 0.0, 1.0, l_f)
        return acc / l_f[..., None]

    # remat per Q block: backward recomputes one block's KV scan at a time,
    # so training never holds more than one (qb x S) score stripe.
    out = jax.lax.map(jax.checkpoint(q_step), jnp.arange(nq))  # (nq,B,Hkv,G,qb,D)
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, sq, d)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


def banded_attention(q, k, v, *, window: int, q_block=512, softcap=None):
    """Sliding-window causal attention, O(S*window).

    For each Q block, slices the KV band [blk_end - window - qb, blk_end)
    with a static size, so compute scales with the window, not the sequence.
    """
    b, hq, sq, d = q.shape
    _, hkv, _, _ = k.shape
    g = hq // hkv
    qb = min(q_block, sq)
    while sq % qb:
        qb //= 2
    band = min(window + qb, sq)
    nq = sq // qb
    qg = q.reshape(b, hkv, g, nq, qb, d).astype(jnp.float32) / math.sqrt(d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    def q_step(iq):
        q_i = qg[:, :, :, iq]
        start = jnp.clip(iq * qb + qb - band, 0, sq - band)
        k_j = jax.lax.dynamic_slice_in_dim(kf, start, band, axis=2)
        v_j = jax.lax.dynamic_slice_in_dim(vf, start, band, axis=2)
        s = jnp.einsum("bkgqd,bkld->bkgql", q_i, k_j)
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = iq * qb + jnp.arange(qb)
        k_pos = start + jnp.arange(band)
        msk = _mask(q_pos, k_pos, causal=True, window=window, prefix_len=None)
        s = jnp.where(msk[None, None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bkgql,bkld->bkgqd", p, v_j)

    out = jax.lax.map(jax.checkpoint(q_step), jnp.arange(nq))
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, sq, d)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# KV cache
# ---------------------------------------------------------------------------

def init_cache(s: AttnSpec, batch: int, max_len: int, dtype=jnp.bfloat16,
               quantized: bool = False, slotted: bool = False,
               ring_slack: int = 0):
    """Ring-buffered when the layer is windowed (cache_len = window).

    quantized=True stores K/V as int8 with per-(batch, head, position)
    scales — the paper's 8-bit numerics applied to the cache (§Perf cell C):
    halves the decode-step HBM traffic, which is the dominant roofline term
    of every decode shape.

    slotted=True gives every batch entry (serve "slot") its own position
    track: ``pos`` becomes (batch, length) so slots can sit at different
    sequence offsets — the layout the continuous-batching engine decodes
    against (DESIGN.md §5).  The lockstep layout keeps the shared (length,)
    ``pos`` and is bit-compatible with the old behavior.

    ring_slack widens windowed rings to ``window + ring_slack`` lines.
    Chunked prefill writes a whole chunk of C keys *before* its queries
    attend, so the chunk's first query still needs the ``window`` keys
    behind it: a ring of exactly ``window`` lines would have evicted up to
    C-1 of them.  Engines writing C positions per call pass
    ``ring_slack=C-1``; the window *mask* is unchanged, so attention
    results are identical to the tight ring.
    """
    length = min(max_len, s.window + ring_slack) if s.window else max_len
    kv_shape = (batch, s.n_kv_heads, length, s.head_dim)
    pos_shape = (batch, length) if slotted else (length,)
    cache = {"pos": jnp.full(pos_shape, -1, jnp.int32)}
    if quantized:
        cache.update({
            "k": jnp.zeros(kv_shape, jnp.int8),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:3], jnp.float32),
            "v_scale": jnp.zeros(kv_shape[:3], jnp.float32),
        })
    else:
        cache.update({"k": jnp.zeros(kv_shape, dtype),
                      "v": jnp.zeros(kv_shape, dtype)})
    return cache


def init_paged_cache(s: AttnSpec, batch: int, max_len: int, *,
                     num_pages: int, page_size: int, dtype=jnp.bfloat16,
                     quantized: bool = False):
    """Paged layout: K/V live in a pool of ``num_pages`` fixed-size pages
    shared by every slot; each slot maps logical block ``j`` (positions
    ``[j*page_size, (j+1)*page_size)``) onto a physical page through its
    block-table row ``bt[slot, j]``.  Page sharing (radix prefix hits,
    ``launch/kvpool.py``) and oversubscription both become block-table
    edits — physical capacity decouples from ``max_slots * max_len``.

    Validity stays the slotted per-slot ``pos`` track (slot, position):
    attention never consults the block table for masking, so stale page
    contents behind invalid positions are harmless, exactly as stale ring
    lines are in the slotted layout.  Windowed layers are not supported:
    their ring semantics would make page contents depend on wrap history,
    which breaks prefix sharing (the engine gates on this).
    """
    if s.window is not None:
        raise NotImplementedError(
            "paged KV cache supports non-windowed attention layers only")
    n_blocks = -(-max_len // page_size)
    kv_shape = (num_pages, s.n_kv_heads, page_size, s.head_dim)
    # unmapped block-table entries hold the sentinel ``num_pages``: writes
    # routed through them scatter out of bounds and DROP (a chunk's padded
    # tail positions may reach past the slot's allocated blocks — they must
    # not land in page 0, which belongs to someone else), and reads clamp
    # to a real page whose lanes the pos-track validity mask kills anyway
    cache = {"pos": jnp.full((batch, max_len), -1, jnp.int32),
             "bt": jnp.full((batch, n_blocks), num_pages, jnp.int32)}
    if quantized:
        cache.update({
            "k": jnp.zeros(kv_shape, jnp.int8),
            "v": jnp.zeros(kv_shape, jnp.int8),
            "k_scale": jnp.zeros(kv_shape[:3], jnp.float32),
            "v_scale": jnp.zeros(kv_shape[:3], jnp.float32),
        })
    else:
        cache.update({"k": jnp.zeros(kv_shape, dtype),
                      "v": jnp.zeros(kv_shape, dtype)})
    return cache


def paged_dense_view(cache) -> dict:
    """Gather a paged cache into the dense slotted layout (B, H, L, D).

    This is the lax twin of ``kernels/paged_attention`` (which copies a
    slot's live pages inside the Pallas grid, the TPU path): the CPU path
    and the kernel's test reference.  Pages are taken through the block
    table in block order, so logical position ``p`` lands at row ``p`` of
    the view — making every downstream op (``cached_attention``, the
    NL-DPE log-domain paths) bit-identical to the dense slotted cache,
    including the exp-grid anchoring to the cache length ``L``.  The view
    is sliced to the ``pos`` track's length, so a page size that does not
    divide ``max_len`` never changes the score-row extent.
    """
    b, length = cache["pos"].shape

    def gather(name):
        x = cache[name][cache["bt"]]            # (B, NB, H, ps[, D])
        x = jnp.moveaxis(x, 2, 1)               # (B, H, NB, ps[, D])
        flat = x.reshape(x.shape[0], x.shape[1], -1, *x.shape[4:])
        return flat[:, :, :length]

    view = {"pos": cache["pos"], "k": gather("k"), "v": gather("v")}
    if "k_scale" in cache:
        view["k_scale"] = gather("k_scale")
        view["v_scale"] = gather("v_scale")
    return view


_POOL_LEAVES = ("k", "v", "k_scale", "v_scale")


def _pool_leaf_axis(path):
    """Pages axis of one pool leaf in a full *model* paged-cache pytree, or
    None for non-pool leaves (``pos``/``bt`` and any non-paged state).
    Stacked layer groups carry pages on axis 1 (their leaves are
    ``(n_groups, num_pages, ...)``); tail layers on axis 0 — the same
    first-key-is-"groups" rule as ``launch.spec_decode.batch_dim`` (not
    imported: nn must stay importable without the launch package)."""
    keys = [k.key for k in path if isinstance(k, jax.tree_util.DictKey)]
    if not keys or keys[-1] not in _POOL_LEAVES:
        return None
    return 1 if keys[0] == "groups" else 0


def gather_page_rows(cache, page) -> list:
    """One physical page's bytes across every pool leaf of a model paged
    cache: the (Hkv, page_size, D) K/V rows — int8 codes plus per-position
    scale rows when the pool is quantized; any ``kv_quant`` mode works
    because whatever pool keys exist are mapped.  Returns a flat list in
    ``jax.tree_util`` path order; ``scatter_page_rows`` consumes the same
    order.  The host spill tier round-trips pages through these two
    (DESIGN.md §13): gather → explicit host copy → scatter restores the
    page bit-identically.
    """
    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        ax = _pool_leaf_axis(path)
        if ax is not None:
            rows.append(jax.lax.dynamic_index_in_dim(
                leaf, page, axis=ax, keepdims=False))
    return rows


def scatter_page_rows(cache, rows, page):
    """Inverse of ``gather_page_rows``: write ``rows`` back as physical
    page ``page`` in every pool leaf (same flat order)."""
    it = iter(rows)

    def one(path, leaf):
        ax = _pool_leaf_axis(path)
        if ax is None:
            return leaf
        row = jnp.asarray(next(it)).astype(leaf.dtype)
        return jax.lax.dynamic_update_index_in_dim(leaf, row, page, axis=ax)

    out = jax.tree_util.tree_map_with_path(one, cache)
    if next(it, None) is not None:
        raise ValueError("scatter_page_rows: rows do not match this cache")
    return out


def _quantize_kv(x: jax.Array, mode: str = "int8"):
    """(B, H, S, D) -> int8 codes + per-(B, H, S) scale.

    ``"int8"``: uniform grid — scale carries absmax / 127, code =
    round(x / scale).  ``"log8"``: the drafter's sign-magnitude log grid
    (``KV_LOG_SPEC``) renormalized per granule — scale carries the absmax,
    |code| indexes the 7-bit log grid of |x| / absmax, and the int8 sign
    carries the sign (0 = flushed zero).  Either way the inverse is
    ``core.quantization.kv_decode`` — the one formula shared by the dense
    view, the ref oracle, and the Pallas kernel's in-tile dequant.
    """
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    if mode == "log8":
        scale = jnp.maximum(absmax, 1e-8)
        code, sign = KV_LOG_SPEC.encode(xf / scale[..., None])
        q = (sign * code.astype(jnp.float32)).astype(jnp.int8)
    elif mode == "int8":
        scale = jnp.maximum(absmax / 127.0, 1e-8)
        q = jnp.clip(jnp.round(xf / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    else:
        raise ValueError(f"unknown kv quant mode {mode!r}")
    return q, scale


def _dequantize_kv(cache, name: str, kv_quant: str | None = None) -> jax.Array:
    if f"{name}_scale" in cache:
        return kv_decode(cache[name], cache[f"{name}_scale"],
                         kv_quant or "int8")
    return cache[name].astype(jnp.float32)


def cache_specs(s: AttnSpec, batch: int, max_len: int, mesh, rules,
                dtype=jnp.bfloat16, slotted: bool = False,
                paged: tuple[int, int] | None = None,
                quantized: bool = False):
    """PartitionSpecs mirroring init_cache / init_paged_cache (kv-head or
    sequence sharded; ``paged=(num_pages, page_size)`` shards the pool's
    leading "pages" axis per the rule table instead of batch).  This is
    the single source of paged spec trees — ``lm.cache_pspecs`` delegates
    here."""
    from jax.sharding import PartitionSpec as P

    from ..parallel.sharding import resolve
    if paged is not None:
        num_pages, page_size = paged
        n_blocks = -(-max_len // page_size)
        kv_shape = (num_pages, s.n_kv_heads, page_size, s.head_dim)
        kv = resolve(rules, ("pages", "kv_heads", None, None), kv_shape, mesh)
        tree = {"k": kv, "v": kv,
                "pos": resolve(rules, ("slots", None), (batch, max_len), mesh),
                "bt": resolve(rules, ("slots", None), (batch, n_blocks), mesh)}
        if quantized:
            sc = resolve(rules, ("pages", "kv_heads", None), kv_shape[:3],
                         mesh)
            tree.update({"k_scale": sc, "v_scale": sc})
        return tree
    length = min(max_len, s.window) if s.window else max_len
    kv_shape = (batch, s.n_kv_heads, length, s.head_dim)
    # prefer kv-head sharding; resolver falls back per divisibility
    kv_axes = ("batch", "kv_heads", None, None)
    if mesh is not None and s.n_kv_heads % mesh.shape.get("model", 1) != 0:
        kv_axes = ("batch", None, "kv_seq", None)
    spec = resolve(rules, kv_axes, kv_shape, mesh)
    pos = (resolve(rules, ("slots", None), (batch, length), mesh)
           if slotted else P())
    return {"k": spec, "v": spec, "pos": pos}


def update_cache(cache, k_new, v_new, pos: jax.Array, write_mask=None,
                 kv_quant: str | None = None):
    """Insert new K/V steps at their ring slots (pos % len).

    Lockstep cache (``pos`` leaf (L,)): ``pos`` must be a scalar — one step
    shared by the whole batch, the original decode contract.

    Slotted cache (``pos`` leaf (B, L)): ``pos`` is (B,) — one step at a
    per-slot offset — or (B, C) — C steps per slot (chunked prefill).
    ``write_mask`` (B,) bool gates the write per slot: masked slots keep
    their cache bit-for-bit (their scatter indices are routed out of bounds
    and dropped), which is how frozen/finished slots survive the shared
    decode step untouched.
    """
    length = cache["k"].shape[2]
    out = dict(cache)
    if cache["pos"].ndim == 1:                      # lockstep layout
        if pos.ndim != 0:
            raise ValueError("lockstep cache takes a scalar pos; build the "
                             "cache with slotted=True for per-slot positions")
        slot = pos % length
        if "k_scale" in cache:
            kq, ks = _quantize_kv(k_new, kv_quant or "int8")
            vq, vs = _quantize_kv(v_new, kv_quant or "int8")
            out["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=2)
            out["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=2)
            out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, slot, axis=2)
            out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, slot, axis=2)
        else:
            out["k"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), slot, axis=2)
            out["v"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), slot, axis=2)
        out["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], pos[None].astype(jnp.int32), slot, axis=0)
        return out

    if "bt" in cache:                               # paged layout
        return _update_cache_paged(cache, k_new, v_new, pos, write_mask,
                                   kv_quant=kv_quant)

    # slotted layout: per-slot scatter, each batch row writes only its own
    # cache line (cross-slot leakage is structurally impossible)
    b = cache["k"].shape[0]
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos[None], (b,))    # shared step, every slot
    pos2 = (pos[:, None] if pos.ndim == 1 else pos).astype(jnp.int32)  # (B, C)
    slots = pos2 % length
    if write_mask is not None:
        # out-of-bounds scatter + mode="drop" = a masked, in-place-safe write
        slots = jnp.where(write_mask[:, None], slots, length)
    bidx = jnp.arange(b)[:, None]
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new, kv_quant or "int8")
        vq, vs = _quantize_kv(v_new, kv_quant or "int8")
        out["k"] = cache["k"].at[bidx, :, slots].set(
            jnp.swapaxes(kq, 1, 2), mode="drop")
        out["v"] = cache["v"].at[bidx, :, slots].set(
            jnp.swapaxes(vq, 1, 2), mode="drop")
        out["k_scale"] = cache["k_scale"].at[bidx, :, slots].set(
            jnp.swapaxes(ks, 1, 2), mode="drop")
        out["v_scale"] = cache["v_scale"].at[bidx, :, slots].set(
            jnp.swapaxes(vs, 1, 2), mode="drop")
    else:
        out["k"] = cache["k"].at[bidx, :, slots].set(
            jnp.swapaxes(k_new, 1, 2).astype(cache["k"].dtype), mode="drop")
        out["v"] = cache["v"].at[bidx, :, slots].set(
            jnp.swapaxes(v_new, 1, 2).astype(cache["v"].dtype), mode="drop")
    out["pos"] = cache["pos"].at[bidx, slots].set(pos2, mode="drop")
    return out


def _update_cache_paged(cache, k_new, v_new, pos: jax.Array, write_mask=None,
                        kv_quant: str | None = None):
    """Scatter new K/V steps through the block table into the page pool.

    ``pos`` is (B,) — one step per slot — or (B, C) — C steps (chunked
    prefill).  Positions are absolute (paged caches are non-windowed, so
    there is no ring modulo): position ``p`` lands in page
    ``bt[slot, p // page_size]`` at offset ``p % page_size``.  Masked or
    out-of-range writes are routed to page id ``num_pages`` and dropped —
    the same OOB-drop freeze the slotted layout uses.  The engine
    guarantees written pages are private to their slot (shared prefix
    pages are read-only by the COW protocol), so no two slots ever scatter
    into the same page.
    """
    num_pages, _, page_size, _ = cache["k"].shape
    b, length = cache["pos"].shape
    out = dict(cache)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos[None], (b,))
    pos2 = (pos[:, None] if pos.ndim == 1 else pos).astype(jnp.int32)  # (B, C)
    n_blocks = cache["bt"].shape[1]
    block = jnp.clip(pos2 // page_size, 0, n_blocks - 1)
    page = jnp.take_along_axis(cache["bt"], block, axis=1)             # (B, C)
    offset = pos2 % page_size
    ok = (pos2 >= 0) & (pos2 < length)
    if write_mask is not None:
        ok = ok & write_mask[:, None]
    page = jnp.where(ok, page, num_pages)          # OOB scatter -> dropped
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k_new, kv_quant or "int8")
        vq, vs = _quantize_kv(v_new, kv_quant or "int8")
        out["k"] = cache["k"].at[page, :, offset].set(
            jnp.swapaxes(kq, 1, 2), mode="drop")
        out["v"] = cache["v"].at[page, :, offset].set(
            jnp.swapaxes(vq, 1, 2), mode="drop")
        out["k_scale"] = cache["k_scale"].at[page, :, offset].set(
            jnp.swapaxes(ks, 1, 2), mode="drop")
        out["v_scale"] = cache["v_scale"].at[page, :, offset].set(
            jnp.swapaxes(vs, 1, 2), mode="drop")
    else:
        out["k"] = cache["k"].at[page, :, offset].set(
            jnp.swapaxes(k_new, 1, 2).astype(cache["k"].dtype), mode="drop")
        out["v"] = cache["v"].at[page, :, offset].set(
            jnp.swapaxes(v_new, 1, 2).astype(cache["v"].dtype), mode="drop")
    bidx = jnp.arange(b)[:, None]
    pos_idx = jnp.where(ok, pos2, length)
    out["pos"] = cache["pos"].at[bidx, pos_idx].set(pos2, mode="drop")
    return out


def _platform() -> str:
    """Platform the traced computation runs on: the ambient mesh's devices
    under a sharding context, else JAX's default backend."""
    from ..parallel.context import current as _sharding_context
    ctx = _sharding_context()
    if ctx is not None:
        return ctx[0].devices.flat[0].platform
    return jax.default_backend()


def paged_kernel_path(nldpe: NLDPEConfig, s: AttnSpec) -> bool:
    """Whether paged decode/chunk attention reads K/V through
    ``kernels/paged_attention`` (each slot's live pages only) rather than
    the gathered ``paged_dense_view``.

    The kernel covers plain softmax attention: NL-DPE numerics, logit
    softcaps and windowed layers keep the dense view.  Within those gates
    the platform decides — a TPU takes the kernel; every other platform
    keeps the dense view, bit-identical to the slotted cache.
    ``NLDPE_PAGED_KERNEL=1`` forces the kernel (interpreted off-TPU) where
    the platform would not choose it, for differential tests."""
    if nldpe.enabled or s.softcap is not None or s.window is not None:
        return False
    if os.environ.get("NLDPE_PAGED_KERNEL", "0") not in ("", "0"):
        return True
    return _platform() == "tpu"


def _paged_kernel_dispatch(cache, q: jax.Array, lengths: jax.Array,
                           kv_quant: str | None = None, write_mask=None):
    """Route paged attention through the Pallas kernel — per-shard under
    ``shard_map`` when an ambient sharding context is installed (GSPMD
    cannot partition a ``pallas_call``), plain otherwise.
    ``q`` is (B, Hq, D) decode or (B, Hq, Q, D) chunk/verify queries.
    Quantized pools hand the kernel the raw int8 code pools plus their
    scales — dequantization happens per block inside the grid, so the fp
    pool is never materialized.  Slots ``write_mask`` freezes attend to
    one position: their outputs are discarded, so the kernel reads at
    most one page for them instead of their stale lengths."""
    from ..kernels.paged_attention.ops import (paged_attention,
                                               paged_attention_sharded)
    from ..parallel.context import current as _sharding_context
    if write_mask is not None:
        lengths = jnp.where(write_mask, lengths, 1)
    ks, vs = cache.get("k_scale"), cache.get("v_scale")
    kv_quant = (kv_quant or "int8") if ks is not None else None
    ctx = _sharding_context()
    if ctx is not None:
        mesh, rules = ctx
        return paged_attention_sharded(q, cache["k"], cache["v"],
                                       cache["bt"], lengths, mesh, rules,
                                       k_scale=ks, v_scale=vs,
                                       kv_quant=kv_quant)
    return paged_attention(q, cache["k"], cache["v"], cache["bt"], lengths,
                           k_scale=ks, v_scale=vs, kv_quant=kv_quant)


def cache_valid_mask(kp: jax.Array, q_pos: jax.Array, window: int | None):
    """Which cache lines each query may attend to.

    kp: (L,) lockstep or (B, L) slotted cache positions (-1 = never written);
    q_pos: () scalar, (Q,) shared, or (B, Q) per-slot query positions
    -> bool (B|1, Q, L).  Callers with per-slot single-token positions must
    pass the explicit (B, 1) form — a 1-d vector always means shared (Q,).
    """
    if kp.ndim == 1:
        kp = kp[None]
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if q_pos.ndim == 0:
        q_pos = q_pos[None, None]
    elif q_pos.ndim == 1:
        q_pos = q_pos[None, :]
    kpe = kp[:, None, :]                               # (B, 1, L)
    qpe = q_pos[:, :, None]                            # (B, Q, 1)
    valid = (kpe >= 0) & (kpe <= qpe)
    if window:
        valid = valid & (qpe - kpe < window)
    return valid


def _nldpe_cached(nldpe: NLDPEConfig, q, att, valid, s: AttnSpec):
    """NL-DPE attention over a dense cache view without repeating K/V.

    GQA folds the group axis into query rows instead of repeating the
    cached K/V to Hq heads (which would materialize a full (B, Hq, L, D)
    fp copy of the pool per layer per tick): query head
    ``kv_head * g + g_idx`` becomes row ``g_idx * Q + j`` of its KV head's
    query block.  The log-domain grids are elementwise and the softmax is
    row-independent, so the folded form is bit-identical to the repeated
    one — and ``nldpe.attention`` sees matching head counts, so its own
    repeat branch never fires.  ``valid``: (B|1, Q, L) per-query validity.
    """
    b, hq, nq, d = q.shape
    g = s.group
    k = _dequantize_kv(att, "k", s.kv_quant).astype(q.dtype)
    v = _dequantize_kv(att, "v", s.kv_quant).astype(q.dtype)
    qf = q.reshape(b, s.n_kv_heads, g, nq, d).reshape(
        b, s.n_kv_heads, g * nq, d)
    msk = jnp.tile(valid, (1, g, 1))       # row g_idx*Q + j uses valid[:, j]
    o = nldpe.attention(qf, k, v, causal=False, mask=msk[:, None])
    return o.reshape(b, s.n_kv_heads, g, nq, d).reshape(b, hq, nq, d)


def cached_attention(q, cache, q_pos: jax.Array, s: AttnSpec, softcap=None):
    """q: (B, Hq, Q, D) against the full cache with validity masking.

    Serves both single-token decode (Q == 1) and chunked prefill (Q == C):
    every query attends to exactly the cache lines whose stored position is
    valid for it (written, causal, in-window), so slots at different offsets
    coexist in one batch.
    """
    b, hq, nq, d = q.shape
    g = s.group
    qg = q.reshape(b, s.n_kv_heads, g, nq, d).astype(jnp.float32)
    k = _dequantize_kv(cache, "k", s.kv_quant)
    v = _dequantize_kv(cache, "v", s.kv_quant)
    scores = jnp.einsum("bkgqd,bkld->bkgql", qg, k) / math.sqrt(d)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    qp = jnp.asarray(q_pos, jnp.int32)
    if qp.ndim == 1 and nq == 1:
        qp = qp[:, None]                  # (B,) per-slot -> explicit (B, 1)
    valid = cache_valid_mask(cache["pos"], qp, s.window)
    scores = jnp.where(valid[:, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgql,bkld->bkgqd", p, v)
    return out.reshape(b, hq, nq, d).astype(q.dtype)


def decode_attention(q, cache, pos: jax.Array, s: AttnSpec, softcap=None):
    """q: (B, Hq, 1, D) against the full cache with validity masking."""
    return cached_attention(q, cache, pos, s, softcap)


# ---------------------------------------------------------------------------
# Block-level entry point
# ---------------------------------------------------------------------------

def attn_apply(p, s: AttnSpec, x: jax.Array, *, positions: jax.Array,
               mode: str = "train", cache=None, prefix_len=None,
               nldpe: NLDPEConfig = OFF, write_mask=None):
    """x: (B, S, d) -> (y, new_cache).

    mode: "train"/"prefill" (full sequence, optional cache fill),
          "decode" (S == 1, cache required), or
          "chunk" (S == chunk, slotted cache required: the chunk's K/V are
          scattered into the cache at per-slot offsets and its queries
          attend to the *whole* cache under validity masking — the
          continuous-batching prefill path, correct at any chunk offset).

    write_mask (B,) bool (slotted caches only): slots where it is False keep
    their cache untouched — frozen/finished serve slots.
    """
    b, seq, _ = x.shape
    q, k, v = _project_qkv(p, s, x, positions)

    if mode == "decode":
        assert cache is not None and seq == 1
        if positions.ndim == 2:
            pos = positions[:, 0]                  # (B,) per-slot offsets
        else:
            pos = positions[0]
        cache = update_cache(cache, k, v, pos, write_mask=write_mask,
                             kv_quant=s.kv_quant)
        if "bt" in cache and pos.ndim == 1 and paged_kernel_path(nldpe, s):
            # stream each slot's live pages through the Pallas kernel
            # (block-table gather inside the grid) instead of materializing
            # the dense view — quantized pools dequantize per block in
            # VMEM.  Matches the dense path within float tolerance, not
            # bitwise; engine caches are contiguous, so valid lanes are
            # [0, pos] per slot.  Under an ambient mesh the kernel
            # dispatches per-shard via shard_map (GSPMD cannot partition a
            # pallas_call), block table replicated across the model axis
            # (DESIGN.md §9).
            o = _paged_kernel_dispatch(cache, q[:, :, 0],
                                       pos.astype(jnp.int32) + 1,
                                       kv_quant=s.kv_quant,
                                       write_mask=write_mask)[:, :, None]
            o = shard(o, "batch", "heads", None, None)
            o = shard(o, "batch", "o_heads", None, None)
            y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(o.dtype))
            return shard(y, "batch", None, "act_embed"), cache
        # elsewhere paged caches attend through the gathered dense view:
        # bit-identical to the slotted layout (the lax twin of
        # kernels/paged_attention)
        att = paged_dense_view(cache) if "bt" in cache else cache
        if nldpe.enabled:
            # NL-DPE decode: log-domain DMMul over the cached keys/values,
            # grouped (GQA folded into query rows — K/V never repeat)
            valid = cache_valid_mask(att["pos"],
                                     pos[:, None] if pos.ndim else pos,
                                     s.window)                     # (B|1,1,L)
            o = _nldpe_cached(nldpe, q, att, valid, s)
        else:
            o = cached_attention(q, att, pos, s, s.softcap)
    elif mode == "chunk":
        assert cache is not None
        if cache["pos"].ndim != 2:
            raise ValueError("chunk mode needs a slotted cache "
                             "(init_cache(..., slotted=True))")
        qpos = (positions if positions.ndim == 2
                else jnp.broadcast_to(positions[None, :], (b, seq)))
        cache = update_cache(cache, k, v, qpos, write_mask=write_mask,
                             kv_quant=s.kv_quant)
        if "bt" in cache and paged_kernel_path(nldpe, s):
            # the kernel path, q_len > 1: chunk queries sit at consecutive
            # per-slot offsets (suffix prefill and the speculative verify
            # pass both write the chunk's K/V first), so query i of slot b
            # attends to [0, qpos[b, 0] + i] — the kernel's ragged
            # staircase with base lengths = qpos[:, 0] + 1.  Same
            # float-tolerance and shard_map notes as decode above.
            lengths = jnp.clip(qpos[:, 0].astype(jnp.int32) + 1, 1,
                               cache["pos"].shape[1])
            o = _paged_kernel_dispatch(cache, q, lengths,
                                       kv_quant=s.kv_quant,
                                       write_mask=write_mask)
            o = shard(o, "batch", "heads", None, None)
            o = shard(o, "batch", "o_heads", None, None)
            y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(o.dtype))
            return shard(y, "batch", None, "act_embed"), cache
        att = paged_dense_view(cache) if "bt" in cache else cache
        if nldpe.enabled:
            valid = cache_valid_mask(att["pos"], qpos, s.window)    # (B,S,L)
            o = _nldpe_cached(nldpe, q, att, valid, s)
        else:
            o = cached_attention(q, att, qpos, s, s.softcap)
    else:
        if nldpe.enabled:
            if s.window is None and prefix_len is None and positions.ndim == 1:
                # plain causal: skip the materialized mask so the dispatcher
                # can stream it through the fused log-domain flash kernel
                # (GQA-aware — K/V stay grouped, no repeat)
                o = nldpe.attention(q, k, v, causal=True, mask=None)
            else:
                kr = jnp.repeat(k, s.group, axis=1)
                vr = jnp.repeat(v, s.group, axis=1)
                msk = _mask(positions if positions.ndim > 1 else positions[None, :],
                            positions if positions.ndim > 1 else positions[None, :],
                            causal=True, window=s.window, prefix_len=prefix_len)
                o = nldpe.attention(q, kr, vr, causal=False,
                                    mask=msk[:, None] if msk.ndim == 3 else msk)
        elif s.window is not None and seq > s.window:
            o = banded_attention(q, k, v, window=s.window, softcap=s.softcap)
        else:
            o = blockwise_attention(q, k, v, causal=True, window=s.window,
                                    prefix_len=prefix_len, softcap=s.softcap)
        if cache is not None:  # prefill populates the cache (ring-consistent)
            if "bt" in cache:
                raise ValueError("paged caches are filled via mode='chunk' "
                                 "or mode='decode', not whole-prompt prefill")
            length = cache["k"].shape[2]
            take = min(seq, length)
            pos_new = jnp.arange(seq - take, seq, dtype=jnp.int32)
            slots = pos_new % length        # position p lives at slot p % len
            if cache["pos"].ndim == 2:      # slotted: same offsets, all slots
                new = {"pos": cache["pos"].at[:, slots].set(pos_new[None])}
            else:
                new = {"pos": cache["pos"].at[slots].set(pos_new)}
            if "k_scale" in cache:
                kq, ks = _quantize_kv(k[:, :, -take:], s.kv_quant or "int8")
                vq, vs = _quantize_kv(v[:, :, -take:], s.kv_quant or "int8")
                new["k"] = cache["k"].at[:, :, slots].set(kq)
                new["v"] = cache["v"].at[:, :, slots].set(vq)
                new["k_scale"] = cache["k_scale"].at[:, :, slots].set(ks)
                new["v_scale"] = cache["v_scale"].at[:, :, slots].set(vs)
            else:
                new["k"] = cache["k"].at[:, :, slots].set(k[:, :, -take:].astype(cache["k"].dtype))
                new["v"] = cache["v"].at[:, :, slots].set(v[:, :, -take:].astype(cache["v"].dtype))
            cache = new

    o = shard(o, "batch", "heads", None, None)
    # contraction boundary: exact serving tables map "o_heads" to None so
    # the head shards all-gather (concatenation — bit-exact) BEFORE the
    # output projection; train tables keep "model" and psum partials as
    # before.  See nn/mlp.py for the same pattern on the down-projection.
    o = shard(o, "batch", "o_heads", None, None)
    y = jnp.einsum("bhsk,hkd->bsd", o, p["wo"].astype(o.dtype))
    return shard(y, "batch", None, "act_embed"), cache
