"""Pallas TPU kernels: the fused ADC-free dual-compute pipeline.

NL-DPE's headline dataflow (paper Fig 3) is *converter-free*: the crossbar's
bit-line currents drive the ACAM word lines directly — there is no ADC (and
in this simulation, no HBM round-trip) between the dot product and the
nonlinearity.  The two kernels here are the software analogue of that wiring
(see DESIGN.md §4):

* ``fused_crossbar_acam_kernel`` — the A-SL dual-conductance VMM of
  ``crossbar_vmm`` with the interval-match + Gray-decode ACAM activation of
  ``acam_activation`` applied in the *final K grid step*.  The f32
  accumulator tile is revisited across the K axis, so it stays in VMEM for
  the whole reduction and the pre-activation tensor never touches HBM.
* ``logdomain_flash_kernel`` — NL-DPE attention (Fig 6c exp-bypass) as a
  streaming three-phase pass over KV blocks: max, quantized-exp sum, and
  exp-bypass output accumulation.  The (Lq, Lk) score matrix is recomputed
  per phase in VMEM and never materialized; only O(Lq) row statistics and
  the output tile persist.

VMEM per grid step (defaults bm=bn=bk=bq=128, f32): fused VMM — x tile
64 KB, four G tiles 256 KB, out 64 KB -> ~0.4 MB, thresholds in SMEM;
log-domain flash — q/k/v tiles 3*64 KB, out 64 KB, two (bq, 1) scratch
stats -> ~0.26 MB.  Both well under the ~16 MB VMEM of a TPU core.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ..acam_activation.kernel import acam_decode_tile

_NEG_INF = float("-inf")

# ACAM decode strip height: the decode carries one accumulator per bit
# plane, so the final-step activation walks the (bm, bn) accumulator in
# one-vreg (8, bn) strips to keep all planes in registers.
_DECODE_STRIP = 8


# ---------------------------------------------------------------------------
# crossbar VMM -> ACAM activation
# ---------------------------------------------------------------------------

def _fused_kernel(x_ref, gp_ref, gn_ref, rp_ref, rn_ref, inv_ref, lo_ref,
                  hi_ref, o_ref, *, res_gain: float, bits: int,
                  out_lo: float, out_step: float):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = (gp_ref[...] - gn_ref[...]) + (rp_ref[...] - rn_ref[...]) * (1.0 / res_gain)
    o_ref[...] += jnp.dot(x_ref[...], w * inv_ref[0, 0],
                          preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _activate():
        # bit-line current drives the ACAM directly: no ADC, no HBM store
        bm = o_ref.shape[0]
        for r0 in range(0, bm, _DECODE_STRIP):
            r1 = min(r0 + _DECODE_STRIP, bm)
            o_ref[r0:r1, :] = acam_decode_tile(
                o_ref[r0:r1, :], lo_ref, hi_ref, bits, out_lo, out_step)


@functools.partial(jax.jit, static_argnames=("res_gain", "bits", "out_lo",
                                             "out_step", "bm", "bn", "bk",
                                             "interpret"))
def fused_crossbar_acam_kernel(x: jax.Array, g_pos: jax.Array,
                               g_neg: jax.Array, g_pos_res: jax.Array,
                               g_neg_res: jax.Array, inv_g_ratio: jax.Array,
                               lo: jax.Array, hi: jax.Array,
                               res_gain: float = 10.0, bits: int = 8,
                               out_lo: float = 0.0, out_step: float = 1.0,
                               bm: int = 128, bn: int = 128, bk: int = 128,
                               interpret: bool | None = None) -> jax.Array:
    """x: (M, K) f32, G cells (K, N) f32, inv_g_ratio (1, 1) f32 (an operand,
    not a static, so traced w_max from in-jit weight programming works),
    lo/hi (bits, rows) f32 -> activated (M, N) f32."""
    m, k = x.shape
    k2, n = g_pos.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    g_spec = pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)   # scalars + tables
    return pl.pallas_call(
        functools.partial(_fused_kernel, res_gain=res_gain, bits=bits,
                          out_lo=out_lo, out_step=out_step),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                  g_spec, g_spec, g_spec, g_spec, smem, smem, smem],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, g_pos, g_neg, g_pos_res, g_neg_res, inv_g_ratio, lo, hi)


# ---------------------------------------------------------------------------
# log-domain flash attention (Fig 6c exp-bypass, streaming)
# ---------------------------------------------------------------------------

def _quant_apply(x, lo: float, hi: float, levels_m1: float):
    """Uniform quantize-dequantize on [lo, hi] (QuantSpec.apply, inlined)."""
    step = (hi - lo) / levels_m1
    code = jnp.clip(jnp.round((x - lo) / step), 0.0, levels_m1)
    return code * step + lo


def _ld_flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, t_ref, *,
                     causal: bool, bq: int, bk: int, lq: int, lk: int,
                     bits: int, score_range: float):
    # m_ref/t_ref: (bq, 1) VMEM scratch — running max and quantized-exp sum
    iq, it = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3) // 3
    phase, j = it // nk, it % nk
    levels_m1 = float((1 << bits) - 1)
    r = score_range

    @pl.when(it == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        t_ref[...] = jnp.zeros_like(t_ref)

    # scores over already-log-quantized reconstructions (DMMul_1, fused
    # mode).  Every later step re-quantizes onto a log/exp grid, so a
    # score error moves codes across grid boundaries: the MXU runs full
    # f32 passes, as the f32 reference does
    s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                            (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    q_pos = (iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
             + (lk - lq))
    k_pos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if causal:
        valid = q_pos >= k_pos
    else:
        valid = jnp.ones((bq, bk), bool)
    s = jnp.where(valid, s, _NEG_INF)

    @pl.when(phase == 0)
    def _max_pass():                                   # Fig 6b step 0 (WTA)
        m_ref[...] = jnp.maximum(m_ref[...],
                                 jnp.max(s, axis=-1, keepdims=True))

    def quantized_scores():
        mx = m_ref[...]
        m_safe = jnp.where(jnp.isfinite(mx), mx, 0.0)
        y = s - m_safe
        return _quant_apply(jnp.where(jnp.isfinite(y), y, -r), -r, 0.0,
                            levels_m1)

    @pl.when(phase == 1)
    def _sum_pass():                                   # steps 1-2: exp + adders
        sq = _quant_apply(jnp.exp(quantized_scores()), 0.0, 1.0, levels_m1)
        sq = jnp.where(valid, sq, 0.0)                 # digital gating
        t_ref[...] += jnp.sum(sq, axis=-1, keepdims=True)

    @pl.when(phase == 2)
    def _out_pass():                                   # steps 3-4 + DMMul_2
        log_total = _quant_apply(jnp.log(jnp.maximum(t_ref[...], 1e-9)),
                                 -r, math.log(lk + 1), levels_m1)
        logp = quantized_scores() - log_total
        a = jnp.exp(_quant_apply(logp, -2.0 * r, 0.0, levels_m1))
        a = jnp.where(valid, a, 0.0)
        o_ref[0, 0] += jnp.dot(a, v_ref[0, 0],
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("causal", "bits", "score_range",
                                             "bq", "bk", "interpret"))
def logdomain_flash_kernel(q_l: jax.Array, k_l: jax.Array, v_l: jax.Array,
                           causal: bool = True, bits: int = 8,
                           score_range: float = 8.0, bq: int = 128,
                           bk: int = 128,
                           interpret: bool | None = None) -> jax.Array:
    """q_l: (B, H, Lq, D); k_l/v_l: (B, Hkv, Lk, D) — all three already
    log-quantized reconstructions (the crossbars' fused log-ACAM outputs).
    The 1/sqrt(d) scale is fused into W_Q upstream (ops wrapper)."""
    b, h, lq, d = q_l.shape
    _, hkv, lk, _ = k_l.shape
    assert h % hkv == 0 and lq % bq == 0 and lk % bk == 0
    group = h // hkv
    nk = lk // bk
    kv_spec = pl.BlockSpec((1, 1, bk, d),
                           lambda bb, hh, iq, it: (bb, hh // group, it % nk, 0))
    return pl.pallas_call(
        functools.partial(_ld_flash_kernel, causal=causal, bq=bq, bk=bk,
                          lq=lq, lk=lk, bits=bits, score_range=score_range),
        grid=(b, h, lq // bq, 3 * nk),
        in_specs=[pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, iq, it: (bb, hh, iq, 0)),
                  kv_spec, kv_spec],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, hh, iq, it: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, lq, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(q_l, k_l, v_l)
