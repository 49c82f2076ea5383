"""Pallas TPU kernel: blockwise causal GQA attention (online softmax).

The serving/training hot spot of every assigned LM architecture.  Classic
flash-attention blocking adapted to TPU: the (nq, nk) score tile lives only
in VMEM/VREGs, never HBM; running max/denominator are carried across the
innermost K-block grid axis in (bq, 1) VMEM scratch.

Grid: (B, H, Lq/bq, Lk/bk), nk innermost.  GQA: the K/V block index maps
collapse query-head groups onto their shared KV head (h // group) — the same
sharing the NL-DPE paper exploits when one log-K ACAM output feeds a whole
query group.

VMEM per step (bq=bk=128, D=128, f32): q/k/v tiles 64 KB each, out 64 KB,
m/l 2*(bq, 1) scratch -> ~0.25 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

_NEG_INF = float("-inf")


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, bq: int, bk: int,
                  lq: int, lk: int):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0] * scale                       # (bq, d)
    k = k_ref[0, 0]                               # (bk, d)
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    if causal:
        # queries sit at the END of the kv axis (decode-friendly alignment)
        q_pos = (iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
                 + (lk - lq))
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

    m_old = m_ref[...]                            # (bq, 1)
    l_old = l_ref[...]
    m_new = jnp.maximum(m_old, jnp.max(s, axis=-1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe)                       # masked s=-inf -> 0
    corr = jnp.where(jnp.isfinite(m_old), jnp.exp(m_old - m_safe), 0.0)
    l_new = l_old * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc = o_ref[0, 0] * corr + jnp.dot(p, v,
                                       preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == nk - 1)
    def _final():
        denom = jnp.where(l_new == 0.0, 1.0, l_new)
        o_ref[0, 0] = acc / denom

    @pl.when(ik != nk - 1)
    def _store():
        o_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q: (B, H, Lq, D); k, v: (B, Hkv, Lk, D); H % Hkv == 0."""
    b, h, lq, d = q.shape
    _, hkv, lk, _ = k.shape
    assert h % hkv == 0 and lq % bq == 0 and lk % bk == 0
    group = h // hkv
    scale = 1.0 / (d ** 0.5)
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bb, hh, iq, ik: (bb, hh // group, ik, 0))
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, lq=lq, lk=lk),
        grid=(b, h, lq // bq, lk // bk),
        in_specs=[pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
                  kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, iq, ik: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
