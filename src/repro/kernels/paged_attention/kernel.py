"""Pallas TPU kernel: paged attention over each sequence's live pages.

The paged serve engine's attention on a TPU (DESIGN.md §7/§8): each
sequence's KV cache is scattered across fixed-size pages of a shared pool,
addressed by a per-sequence block table.  The pool stays in HBM
(``memory_space=ANY``) and the kernel fetches the pages it needs itself,
one async copy per page through the block table (a **scalar-prefetch**
operand), into a double-buffered VMEM block — the pattern of the
paged-attention kernel shipped with JAX, kept on this repo's page-major
``(P, Hkv, ps, D)`` pool, where one page holds every KV head contiguously,
so a page is ONE copy and every head is computed in the same step.

The kernel reads the pool through its ``(P, ps, Hkv, D)`` view: inside
the serving programs XLA stores the scan-carried pool with each
position's KV heads adjacent (the layout its one-position scatter
writes), and this view of it is a bitcast, where the ``(P, Hkv, ps, D)``
operand would be a copy of the layer's whole pool per call.  A copy must
move whole tiles, so head counts whose rows do not fill the view's tiles
(``_heads_minor``), and int8 code pools, read ``(P, Hkv, ps, D)`` as is;
a head_dim short of 128 lanes is zero-padded to them when compiled.

Grid: ``(B,)``, one step per sequence.  Inside a step a loop walks compute
blocks of ``ppcb`` pages (``block_pages``: ~256 tokens, sized from the
page and head shapes) up to the sequence's live page count
``ceil((lengths[b] + q_len - 1) / ps)``; no block past it is copied or
computed.  The last block's page slots past it are refilled with the
sequence's first page and masked, so every copy is unconditional (no
branch per page).  While block ``i`` is computed, block ``i + 1`` — or
the next sequence's first block — is already in flight into the other
buffer.

Queries ride grouped per KV head (GQA) *and* per query position: the q
block is each head's (group * q_len, D) rows — single-token decode is
``q_len == 1``, and the ragged chunk-prefill / speculative-verify grid
batches a chunk's C positions as ``q_len == C``.  Row ``g*q_len + j`` may
attend to logical positions ``< lengths[b] + j`` (query ``j`` sits ``j``
positions past the base length): the causal staircase across the
in-flight chunk and the partially-filled tail page fall out of one mask.
Scores are bf16 (or the pool's float) dots with f32 accumulation; online
softmax carries running max/denominator across blocks in VMEM scratch.

Quantized pools (DESIGN.md §11): with ``kv_quant`` set the pages hold int8
codes, decoded per block in VMEM with the shared
``core.quantization.kv_decode`` formula — the full fp pool never exists
anywhere.  A copy must span whole 128-lane rows, so each page's
(Hkv, ps) scales ride as one zero-padded lane row; a one-hot dot lays
head ``h``'s scales out along the block's positions, where they scale the
score columns (K) and the probability columns (V) — the same products as
scaling the decoded rows.

Block-table entries outside ``[0, num_pages)`` are the unmapped-block
sentinel: their copy reads a clamped page and the body masks their
positions out of the softmax, mirroring the write path's OOB-drop
scatter.  Value rows past the last position any query may see are
zeroed before the ``p @ v`` dot, so stale or poisoned bytes in a fetched
tail page never reach the result.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ...core.quantization import kv_decode

_NEG_INF = float("-inf")
# a compute block holds ~this many tokens, and one K buffer at most this
# many bytes (double-buffered K and V: 4x in VMEM)
_BLOCK_TOKENS = 256
_BLOCK_BYTES = 1 << 20


def block_pages(page_size: int, n_kv_heads: int, head_dim: int,
                itemsize: int, q_rows: int, n_blocks: int) -> int:
    """Pages per compute block: a power of two up to ~256 tokens, held to
    1 MiB per K buffer and to 1 MiB of f32 scores per head (``q_rows`` =
    group * q_len query rows), never more than the block table has."""
    page_bytes = n_kv_heads * page_size * head_dim * itemsize
    n = min(_BLOCK_TOKENS // page_size, _BLOCK_BYTES // page_bytes,
            _BLOCK_BYTES // (4 * q_rows * page_size), n_blocks)
    n = max(n, 1)
    return 1 << (n.bit_length() - 1)


def _heads_minor(n_kv_heads: int, itemsize: int) -> bool:
    """Whether a page of the (P, ps, Hkv, D) view is whole tiles, so one
    async copy moves it (by compiles for a v5e): f32 heads always; 16-bit
    heads, packed two rows a word, at 2 or 4 heads or a multiple of 8;
    int8 never, as four rows pack a word and split a position's heads."""
    if itemsize == 4:
        return True
    return itemsize == 2 and (n_kv_heads in (2, 4) or n_kv_heads % 8 == 0)


def _scale_row(rows: jax.Array, h: int, ps: int) -> jax.Array:
    """(1, ppcb * ps) scales of head ``h`` along a block's positions, from
    the block's (ppcb, lanes) scale rows (lane ``h * ps + o`` holds offset
    ``o`` of head ``h``): a one-hot dot picks each position's lane, a
    masked sublane sum its page."""
    ppcb, lanes = rows.shape
    bk = ppcb * ps
    pick = (jax.lax.broadcasted_iota(jnp.int32, (lanes, bk), 1) % ps
            + h * ps == jax.lax.broadcasted_iota(jnp.int32, (lanes, bk), 0))
    spread = jax.lax.dot_general(
        rows, pick.astype(jnp.float32), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)            # (ppcb, bk)
    page = (jax.lax.broadcasted_iota(jnp.int32, (ppcb, bk), 1) // ps
            == jax.lax.broadcasted_iota(jnp.int32, (ppcb, bk), 0))
    return jnp.sum(jnp.where(page, spread, 0.0), axis=0, keepdims=True)


def _paged_kernel(len_ref, bt_ref, hole_ref, q_ref, k_hbm, v_hbm, *rest,
                  scale: float, ps: int, q_len: int, nb: int, ppcb: int,
                  num_pages: int, kv_quant: str | None, heads_minor: bool):
    if kv_quant is not None:
        (ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         m_ref, l_ref, acc_ref, slot_ref) = rest
    else:
        o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref, slot_ref = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    b = pl.program_id(0)
    n_seq = pl.num_programs(0)
    hkv, gq = q_ref.shape[1], q_ref.shape[2]
    bk = ppcb * ps

    def live_pages(bb):
        n = (jnp.maximum(len_ref[bb], 1) + q_len - 1 + ps - 1) // ps
        return jnp.minimum(n, nb)

    def entry(bb, logical):
        return bt_ref[bb * nb + jnp.minimum(logical, nb - 1)]

    def fetch(bb, i, slot, wait: bool):
        """Copy block ``i`` of sequence ``bb``: every page slot of the
        buffer is refilled — a page past the live ones re-reads the
        sequence's first page and a sentinel a clamped one, both masked —
        so the copies are unconditional."""
        n = live_pages(bb)
        pairs = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
        if ks_hbm is not None:
            pairs += [(ks_hbm, ks_buf, 0), (vs_hbm, vs_buf, 1)]

        def page(j, carry):
            logical = i * ppcb + j
            idx = jnp.clip(jnp.where(logical < n, entry(bb, logical),
                                     entry(bb, 0)), 0, num_pages - 1)
            for src, dst, sem in pairs:
                cp = pltpu.make_async_copy(src.at[idx], dst.at[slot, j],
                                           sems.at[sem, slot])
                cp.wait() if wait else cp.start()
            return carry

        jax.lax.fori_loop(0, ppcb, page, 0)

    @pl.when(b == 0)
    def _first():
        slot_ref[0] = 0
        fetch(0, 0, 0, wait=False)

    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    length = jnp.maximum(len_ref[b], 1)
    n_live = live_pages(b)
    n_blocks = (n_live + ppcb - 1) // ppcb

    def heads(buf, slot, row_ok):
        """Head ``h``'s (bk, D) rows of the block in ``slot``, value rows no
        query may see zeroed (0 * NaN would survive a zero probability)."""
        if not heads_minor:                     # (ppcb, Hkv, ps, D) pages
            def get(h):
                x = buf[slot, :, h]
                if kv_quant is not None:
                    x = kv_decode(x, None, kv_quant)
                x = x.reshape(bk, x.shape[-1])
                return jnp.where(row_ok, x, jnp.zeros_like(x))
            return get
        rows = buf.at[slot].reshape(bk * hkv, buf.shape[-1])
        if buf.dtype.itemsize == 4:             # (ppcb, ps, Hkv, D) pages
            def get(h):
                x = rows[pl.ds(h, bk, stride=hkv), :]
                return jnp.where(row_ok, x, jnp.zeros_like(x))
            return get
        # 16-bit heads pack two to a 32-bit word: read a pair's words with
        # one strided load and split them (a bf16 is the top half of an f32)
        words = rows.bitcast(jnp.uint32)

        def get(h):
            w = words[pl.ds(h // 2, bk, stride=hkv // 2), :]
            w = jnp.where(row_ok, w, jnp.zeros_like(w))
            w = (w << 16) if h % 2 == 0 else (w & jnp.uint32(0xFFFF0000))
            return pltpu.bitcast(w, jnp.float32).astype(buf.dtype)
        return get

    def block(i, carry):
        slot = slot_ref[0]
        nxt = 1 - slot

        @pl.when(i + 1 < n_blocks)
        def _prefetch_block():
            fetch(b, i + 1, nxt, wait=False)

        @pl.when((i + 1 == n_blocks) & (b + 1 < n_seq))
        def _prefetch_sequence():
            fetch(b + 1, 0, nxt, wait=False)

        fetch(b, i, slot, wait=True)

        base = i * bk
        pos = base + jax.lax.broadcasted_iota(jnp.int32, (gq, bk), 1)
        limit = length
        if q_len > 1:                           # row g*q_len + j: query j
            limit = limit + jax.lax.broadcasted_iota(
                jnp.int32, (gq, bk), 0) % q_len
        ok = pos < limit

        def holes():
            """Columns of the block's sentinel pages inside the live range
            (a sequence's table maps every live block in serving)."""
            page = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1) // ps

            def mark(j, bad):
                e = entry(b, i * ppcb + j)
                hole = ((i * ppcb + j < n_live)
                        & ((e < 0) | (e >= num_pages))).astype(jnp.int32)
                return bad + (page == j).astype(jnp.int32) * hole

            return jax.lax.fori_loop(0, ppcb, mark,
                                     jnp.zeros((1, bk), jnp.int32))

        bad = jax.lax.cond(hole_ref[b] > 0, holes,
                           lambda: jnp.zeros((1, bk), jnp.int32))
        ok = ok & (bad == 0)
        row = base + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
        row_ok = row < length + q_len - 1
        k_head, v_head = heads(k_buf, slot, row_ok), heads(v_buf, slot, row_ok)

        if kv_quant is not None:
            k_sc = ks_buf[slot].reshape(ppcb, ks_buf.shape[-1])
            v_sc = vs_buf[slot].reshape(ppcb, vs_buf.shape[-1])
        for h in range(hkv):
            k, v = k_head(h), v_head(h)                # (bk, D)
            q = q_ref[0, h].astype(k.dtype)            # (G*q_len, D)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if kv_quant is not None:
                s = s * _scale_row(k_sc, h, ps)
            s = jnp.where(ok, s, _NEG_INF)

            m_old = m_ref[h]                           # (G*q_len, 1)
            m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)                    # masked s=-inf -> 0
            corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_safe),
                             0.0)
            l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
            if kv_quant is not None:
                p = p * _scale_row(v_sc, h, ps)
            acc_ref[h] = acc_ref[h] * corr + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        slot_ref[0] = nxt
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l_fin = l_ref[...]
    o_ref[0] = acc_ref[...] / jnp.where(l_fin == 0.0, 1.0, l_fin)


@functools.partial(jax.jit, static_argnames=("kv_quant", "interpret"))
def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_tables: jax.Array,
                           lengths: jax.Array,
                           k_scale: jax.Array | None = None,
                           v_scale: jax.Array | None = None,
                           kv_quant: str | None = None,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, Q, D); k_pages/v_pages: (P, Hkv, ps, D) — fp values, or
    int8 codes when ``kv_quant`` names a grid and k_scale/v_scale carry the
    (P, Hkv, ps) per-(page, head, position) scales; block_tables: (B, NB)
    int32 (entries outside [0, P) are the unmapped sentinel and contribute
    nothing); lengths: (B,) int32, 1 <= lengths[b] <= NB*ps — query row
    ``j`` of sequence ``b`` attends to logical positions
    ``[0, lengths[b] + j)``.  Returns (B, Hq, Q, D) f32.
    """
    b, hq, q_len, d_head = q.shape
    num_pages, hkv, ps, _ = k_pages.shape
    assert hq % hkv == 0
    g = hq // hkv
    gq = g * q_len
    nb = block_tables.shape[1]
    interpret = resolve_interpret(interpret)
    if d_head % 128 and not interpret:
        # a copy moves whole 128-lane rows: zero lanes add nothing to q.k
        # and leave zero output lanes
        lanes = [(0, 0)] * 3 + [(0, -d_head % 128)]
        q, k_pages, v_pages = (jnp.pad(x, lanes)
                               for x in (q, k_pages, v_pages))
    d = q.shape[-1]
    ppcb = block_pages(ps, hkv, d, k_pages.dtype.itemsize, gq, nb)
    # (B, Hkv, G*q_len, D): row r = g*q_len + j keeps query j of group g
    qg = q.reshape(b, hkv, gq, d)
    q_spec = pl.BlockSpec((1, hkv, gq, d),
                          lambda bb, ln, bt, hole: (bb, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [q_spec, hbm, hbm]
    # per sequence: does a sentinel entry lie inside its live pages?
    lengths = lengths.astype(jnp.int32)
    bt = block_tables.astype(jnp.int32)
    live = -(-(jnp.maximum(lengths, 1) + q_len - 1) // ps)
    holes = jnp.any(((bt < 0) | (bt >= num_pages))
                    & (jnp.arange(nb) < live[:, None]), axis=1)
    holes = holes.astype(jnp.int32)
    heads_minor = _heads_minor(hkv, k_pages.dtype.itemsize)
    if heads_minor:
        k_pages, v_pages = (jnp.swapaxes(x, 1, 2) for x in (k_pages, v_pages))
    operands = [qg, k_pages, v_pages]
    buf = (2,) + (ppcb,) + k_pages.shape[1:]
    scratch = [pltpu.VMEM(buf, k_pages.dtype), pltpu.VMEM(buf, v_pages.dtype)]
    if kv_quant is not None:
        # one zero-padded 128-lane row of (Hkv, ps) scales per page
        lanes = -(-hkv * ps // 128) * 128

        def rows(sc):
            sc = sc.astype(jnp.float32).reshape(num_pages, 1, hkv * ps)
            return jnp.pad(sc, ((0, 0), (0, 0), (0, lanes - hkv * ps)))

        in_specs += [hbm, hbm]
        operands += [rows(k_scale), rows(v_scale)]
        scratch += [pltpu.VMEM((2, ppcb, 1, lanes), jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hkv, gq, 1), jnp.float32),
                pltpu.VMEM((hkv, gq, 1), jnp.float32),
                pltpu.VMEM((hkv, gq, d), jnp.float32),
                pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=1.0 / (d_head ** 0.5), ps=ps,
                          q_len=q_len, nb=nb, ppcb=ppcb,
                          num_pages=num_pages, kv_quant=kv_quant,
                          heads_minor=heads_minor),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gq, d), jnp.float32),
        # the next sequence's first block is fetched by this one's last
        # step: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lengths, bt.reshape(-1), holes, *operands)
    return out[..., :d_head].reshape(b, hq, q_len, d_head)
