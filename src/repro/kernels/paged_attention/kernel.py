"""Pallas TPU kernel: paged attention (gather via block table), q_len >= 1.

The streaming attention path of the paged serve engine (DESIGN.md §7/§8,
opt-in via ``NLDPE_PAGED_KERNEL=1`` — the engine defaults to the
bit-exact gathered dense view in ``nn.attention.paged_dense_view``): each
sequence's KV cache is scattered across fixed-size pages of a shared pool,
addressed by a per-sequence block table.  The kernel never materializes the
gathered cache — the block table rides in as a **scalar-prefetch** operand,
so the BlockSpec index map itself performs the gather: grid step
``(b, h, i)`` DMAs physical page ``block_tables[b, i]`` straight from the
pool into VMEM while the previous page is still being consumed (the
standard Pallas double-buffering pipeline makes the indirection free).

Grid: (B, Hkv, NB), pages innermost.  Queries ride grouped per KV head
(GQA) *and* per query position: the q block is that head's (group * q_len,
D) rows — single-token decode is ``q_len == 1``, and the ragged prefill /
speculative-verify grid of ``launch/spec_decode.py`` batches a chunk's C
positions as ``q_len == C`` so one fetched page feeds every query of the
step.  Ragged masking is per query row: row ``g*q_len + j`` may attend to
logical positions ``< lengths[b] + j`` (query ``j`` sits ``j`` positions
past the base length), which makes the causal staircase across the
in-flight chunk fall out of the same mask that handles partially-filled
tail pages.  Online softmax carries running max/denominator across the
page axis in VMEM scratch (``(G*q_len, 1)`` columns: a stats *output*
block of ``(1, 1, G*q_len)`` would break the TPU rule that a block's last
two dims are (8, 128)-aligned or whole), exactly like
``kernels/flash_attention``.

Quantized pools (DESIGN.md §11): with ``kv_quant`` set the page tiles hold
int8 codes and two extra scale operands ride the same block-table index
map, so each grid step dequantizes ONE (ps, D) page in VMEM with the
shared ``core.quantization.kv_decode`` formula — the full fp pool never
exists anywhere.  The scales ride as (P, Hkv, ps, 1) so their block's last
two dims are whole and the (ps, 1) tile broadcasts over the page's lanes.
Block-table entries outside ``[0, num_pages)`` are the unmapped-block
sentinel: the index map clamps them (the DMA must stay in bounds) and the
body masks the whole page out of the softmax, mirroring the write path's
OOB-drop scatter.

VMEM per step (ps=64, D=128, G=8, q_len=5, f32): k/v page tiles 32 KB
each (8 KB as int8 codes + 256 B scales), q/out 20 KB, m/l tiny -> well
under budget at any production shape.
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


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale: float, ps: int, q_len: int, num_pages: int,
                  kv_quant: str | None):
    if kv_quant is not None:
        ks_ref, vs_ref, o_ref, m_ref, l_ref = rest
    else:
        o_ref, m_ref, l_ref = rest                 # m/l: (G*q_len, 1) scratch
    bb, i = pl.program_id(0), pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0] * scale                        # (G*q_len, d)
    if kv_quant is not None:                       # dequant ONE page in VMEM
        k = kv_decode(k_ref[0, 0], None, kv_quant) * ks_ref[0, 0]
        v = kv_decode(v_ref[0, 0], None, kv_quant) * vs_ref[0, 0]
    else:
        k = k_ref[0, 0]                            # (ps, d)
        v = v_ref[0, 0]
    gq = q.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (G*q_len, ps)

    # logical positions of this page; query row g*q_len + j attends to
    # positions < lengths[b] + j (TPU needs >= 2-d iota: broadcasted)
    pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, (gq, ps), 1)
    qoff = jax.lax.broadcasted_iota(jnp.int32, (gq, ps), 0) % q_len
    # sentinel (unmapped) block: the index map clamped the DMA to a real
    # page, so kill the whole page here instead of aliasing its contents
    blk = bt_ref[bb, i]
    ok = (pos < len_ref[bb] + qoff) & (blk >= 0) & (blk < num_pages)
    s = jnp.where(ok, s, _NEG_INF)

    m_old = m_ref[...]                             # (G*q_len, 1)
    l_old = l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)                        # masked s=-inf -> 0
    corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_safe), 0.0)
    l_new = l_old * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = o_ref[0, 0] * corr + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(i == nb - 1)
    def _final():
        denom = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0, 0] = acc / denom

    @pl.when(i != nb - 1)
    def _store():
        o_ref[0, 0] = acc


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
    b, hq, q_len, d = q.shape
    num_pages, hkv, ps, _ = k_pages.shape
    assert hq % hkv == 0
    g = hq // hkv
    nb = block_tables.shape[1]
    scale = 1.0 / (d ** 0.5)
    # (B, Hkv, G*q_len, D): row r = g*q_len + j keeps query j of group g
    qg = q.reshape(b, hkv, g, q_len, d).reshape(b, hkv, g * q_len, d)

    def page_idx(bb, hh, i, bt, ln):
        # sentinel entries clamp for the DMA; the body masks them fully
        return (jnp.clip(bt[bb, i], 0, num_pages - 1), hh, 0, 0)

    kv_spec = pl.BlockSpec((1, 1, ps, d), page_idx)
    in_specs = [pl.BlockSpec((1, 1, g * q_len, d),
                             lambda bb, hh, i, bt, ln: (bb, hh, 0, 0)),
                kv_spec, kv_spec]
    operands = [qg, k_pages, v_pages]
    if kv_quant is not None:
        sc_spec = pl.BlockSpec((1, 1, ps, 1), page_idx)
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale[..., None], v_scale[..., None]]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g * q_len, d),
                               lambda bb, hh, i, bt, ln: (bb, hh, 0, 0)),
        scratch_shapes=[pltpu.VMEM((g * q_len, 1), jnp.float32),
                        pltpu.VMEM((g * q_len, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, scale=scale, ps=ps, q_len=q_len,
                          num_pages=num_pages, kv_quant=kv_quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g * q_len, d), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_tables, lengths, *operands)
    return out.reshape(b, hkv, g, q_len, d).reshape(b, hq, q_len, d)
