"""Public op: jit'd paged attention wrapper (decode and multi-query).

Unlike the dense attention wrappers there is no block-size fallback to
pick: the page *is* the KV block, so any page size works as-is (odd sizes
included — masking, not padding, handles partially-filled tail pages).
Pools reach the kernel in their stored dtype — a cast here would copy the
whole pool — and the kernel computes scores with f32 accumulation;
quantized pools stay int8 all the way in, dequantized one block at a
time in VMEM.  Raw
block tables flow through unchanged: entries outside ``[0, num_pages)``
are the unmapped-block sentinel, and both the kernel and the ref oracle
mask those pages out of the softmax (the read-side mirror of the write
path's OOB-drop scatter) — clamping them here would silently alias the
sentinel onto the last real page and read another slot's data.

``q`` may be (B, Hq, D) — single-token decode, the PR 3 signature — or
(B, Hq, Q, D) with ``Q > 1`` for the ragged chunk-prefill / speculative
verify grid: query row ``j`` attends to logical positions
``[0, lengths[b] + j)``, the causal staircase over the in-flight chunk.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .kernel import paged_attention_kernel
from .ref import paged_attention_ref


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array,
                    k_scale: jax.Array | None = None,
                    v_scale: jax.Array | None = None,
                    kv_quant: str | None = None,
                    interpret: bool | None = None,
                    use_ref: bool = False) -> jax.Array:
    """q: (B, Hq, D) decode queries or (B, Hq, Q, D) multi-query;
    k_pages/v_pages: (P, Hkv, ps, D) page pools — fp values, or int8 codes
    when ``kv_quant`` ("int8"/"log8") is set and k_scale/v_scale carry the
    (P, Hkv, ps) per-(page, head, position) scales; block_tables: (B, NB)
    int32 (entries outside [0, P) are the unmapped sentinel and contribute
    nothing); lengths: (B,) int32 — query row ``j`` of sequence ``b``
    attends to logical positions ``[0, lengths[b] + j)`` (lengths >= 1).
    Returns the same rank as ``q`` in ``q.dtype``.
    """
    if kv_quant is None and k_scale is not None:
        kv_quant = "int8"
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, :, None]
    bt = block_tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if use_ref:
        out = paged_attention_ref(q.astype(jnp.float32), k_pages, v_pages,
                                  bt, lengths, k_scale=k_scale,
                                  v_scale=v_scale, kv_quant=kv_quant)
    else:
        out = paged_attention_kernel(q, k_pages, v_pages, bt, lengths,
                                     k_scale=k_scale, v_scale=v_scale,
                                     kv_quant=kv_quant, interpret=interpret)
    out = out.astype(q.dtype)
    return out[:, :, 0] if squeeze else out


def _divides(mesh, axis, *dims) -> bool:
    """True when ``axis`` exists on ``mesh`` and divides every dim."""
    if axis is None:
        return False
    flat = tuple(axis) if isinstance(axis, (tuple, list)) else (axis,)
    if any(a not in mesh.shape for a in flat):
        return False
    from ...parallel.sharding import _axis_size
    size = _axis_size(mesh, axis)
    return all(d % size == 0 for d in dims)


def paged_attention_sharded(q: jax.Array, k_pages: jax.Array,
                            v_pages: jax.Array, block_tables: jax.Array,
                            lengths: jax.Array, mesh, rules,
                            k_scale: jax.Array | None = None,
                            v_scale: jax.Array | None = None,
                            kv_quant: str | None = None,
                            interpret: bool | None = None,
                            use_ref: bool = False) -> jax.Array:
    """``paged_attention`` under ``shard_map``: the Pallas grid runs once
    per shard on that shard's heads and sequences (DESIGN.md §9).

    A ``pallas_call`` cannot be partitioned by GSPMD, so under a mesh the
    kernel is dispatched per-shard explicitly: query/kv heads shard over
    the rule table's "kv_heads" mesh axis (both head counts must divide so
    every GQA group stays shard-local), sequences over "slots".  The block
    table and lengths ride **replicated across the model axis** — every
    head shard gathers through the same table into its own head slice of
    the page pools, and the gather indices carry no float math, so the
    per-shard outputs are exactly the head slices of the unsharded call.
    Quantized pools shard their (P, Hkv, ps) scales on the same head axis
    as the code pools.  The pools' pages axis is always replicated here (a
    "pages"->data mapping, as in the LONG rules, is resharded in at the
    boundary).  Any non-divisible axis falls back to replication — never
    an error.
    """
    from jax.sharding import PartitionSpec as P

    if kv_quant is None and k_scale is not None:
        kv_quant = "int8"
    b, hq = q.shape[0], q.shape[1]
    hkv = k_pages.shape[1]
    model_ax = rules.lookup("kv_heads")
    if not _divides(mesh, model_ax, hq, hkv):
        model_ax = None
    data_ax = rules.lookup("slots")
    if not _divides(mesh, data_ax, b):
        data_ax = None
    q_spec = P(data_ax, model_ax, *(None,) * (q.ndim - 2))
    kv_spec = P(None, model_ax, None, None)
    sc_spec = P(None, model_ax, None)

    if kv_quant is not None:
        def local(q_, kp_, vp_, bt_, ln_, ks_, vs_):
            return paged_attention(q_, kp_, vp_, bt_, ln_, k_scale=ks_,
                                   v_scale=vs_, kv_quant=kv_quant,
                                   interpret=interpret, use_ref=use_ref)

        return jax.shard_map(
            local, mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec, P(data_ax, None),
                      P(data_ax), sc_spec, sc_spec),
            out_specs=q_spec, check_vma=False,
        )(q, k_pages, v_pages, block_tables, lengths, k_scale, v_scale)

    def local(q_, kp_, vp_, bt_, ln_):
        return paged_attention(q_, kp_, vp_, bt_, ln_,
                               interpret=interpret, use_ref=use_ref)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, P(data_ax, None), P(data_ax)),
        out_specs=q_spec, check_vma=False,
    )(q, k_pages, v_pages, block_tables, lengths)
