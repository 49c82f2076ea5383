"""Public ops for the fused dual-compute pipeline.

Three entry points (DESIGN.md §4):

* ``fused_crossbar_acam``  — plan-level: SlicedWeights + ACAMTable -> one
  fused pass (the direct replacement for crossbar_matmul -> acam_apply).
* ``fused_linear_acam``    — model-level: a plain weight matrix is programmed
  to ideal A-SL conductances *inside jit* (traced w_max; the kernel takes
  1/g_ratio as an operand) and routed through the fused kernel.  This is the
  path NLDPEConfig.linear_activation dispatches to.
* ``logdomain_flash_attention`` — NL-DPE attention with the Fig 6c
  exp-bypass streamed inside the online loop; drop-in for nldpe_attention
  on causal/full (maskless) shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dt import ACAMTable
from ...core.logdomain import DEFAULT_CFG, LogDomainConfig, log_quantize
from ...core.noise import DEFAULT, NoiseModel
from ...core.slicing import RESIDUAL_GAIN, SlicedWeights, plan_asl
from .. import divisor_block
from .kernel import fused_crossbar_acam_kernel, logdomain_flash_kernel
from .ref import fused_crossbar_acam_ref, logdomain_flash_ref

_LANE = 128


def _thresholds(table: ACAMTable):
    from ...core.acam import table_thresholds_jnp
    return table_thresholds_jnp(table)


def _pad_and_run(x2: jax.Array, cells, inv, table: ACAMTable, g_min: float,
                 interpret: bool | None) -> jax.Array:
    """Pad to lane multiples (conductance padding at g_min decodes to weight
    0), run the fused kernel, crop.  x2: (M, K) f32; cells: four (K, N)."""
    lo, hi = _thresholds(table)
    m, k = x2.shape
    n = cells[0].shape[1]
    pm, pk, pn = (-m) % _LANE, (-k) % _LANE, (-n) % _LANE
    xp = jnp.pad(x2, ((0, pm), (0, pk)))
    cells_p = [jnp.pad(g, ((0, pk), (0, pn)), constant_values=g_min)
               for g in cells]
    out = fused_crossbar_acam_kernel(
        xp, *cells_p, jnp.asarray(inv, jnp.float32).reshape(1, 1), lo, hi,
        res_gain=RESIDUAL_GAIN, bits=table.bits,
        out_lo=float(table.out_spec.lo), out_step=float(table.out_spec.step),
        interpret=interpret)
    return out[:m, :n]


def fused_crossbar_acam(x: jax.Array, plan: SlicedWeights, table: ACAMTable,
                        rng: jax.Array | None = None,
                        model: NoiseModel = DEFAULT,
                        interpret: bool | None = None,
                        use_ref: bool = False) -> jax.Array:
    """acam(x @ W_eff) in one pass: the pre-activation never leaves VMEM.

    Mirrors crossbar_matmul's contract (per-call read noise drawn here,
    padding cells at g_min so they decode to weight 0) with the activation
    applied to the in-VMEM accumulator.
    """
    cells = [plan.g_pos_main, plan.g_neg_main, plan.g_pos_res, plan.g_neg_res]
    if rng is not None:
        keys = jax.random.split(rng, 4)
        cells = [model.read(k, g) for k, g in zip(keys, cells)]
    inv = plan.w_max / (model.g_max - model.g_min)
    if use_ref:
        lo, hi = _thresholds(table)
        return fused_crossbar_acam_ref(x, *cells, inv, lo, hi, table.bits,
                                       float(table.out_spec.lo),
                                       float(table.out_spec.step),
                                       RESIDUAL_GAIN)
    return _pad_and_run(x.astype(jnp.float32), cells, inv, table,
                        model.g_min, interpret)


def fused_linear_acam(x: jax.Array, w: jax.Array, act: str, bits: int = 8,
                      in_domain: tuple[float, float] | None = None,
                      interpret: bool | None = None,
                      use_ref: bool = False) -> jax.Array:
    """acam_act(x @ w) through an ideally-programmed A-SL crossbar, fused.

    x: (..., K) any leading shape; w: (K, N).  Programming is noise-free
    (W_eff == w exactly), jit-traceable (w_max stays a traced scalar), and
    happens per call — the simulation analogue of the deployed chip reading
    its already-programmed cells.  ``use_ref`` runs the same programmed
    cells through the two-step jnp oracle instead of the kernel.
    """
    from ...core.acam import get_table

    table = get_table(act, bits, "gray", in_domain)
    w = w.astype(jnp.float32)
    w_max = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
    plan, _ = plan_asl(w, w_max, DEFAULT, prog_rng=None)
    inv = w_max / (DEFAULT.g_max - DEFAULT.g_min)

    shape = x.shape
    x2 = x.reshape(-1, shape[-1]).astype(jnp.float32)
    cells = [plan.g_pos_main, plan.g_neg_main, plan.g_pos_res, plan.g_neg_res]
    if use_ref:
        lo, hi = _thresholds(table)
        out = fused_crossbar_acam_ref(x2, *cells, inv, lo, hi, table.bits,
                                      float(table.out_spec.lo),
                                      float(table.out_spec.step),
                                      RESIDUAL_GAIN)
    else:
        out = _pad_and_run(x2, cells, inv, table, DEFAULT.g_min, interpret)
    return out.reshape(*shape[:-1], w.shape[1])


def logdomain_flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                              cfg: LogDomainConfig = DEFAULT_CFG,
                              causal: bool = True, bq: int = 128,
                              bk: int = 128, interpret: bool | None = None,
                              use_ref: bool = False) -> jax.Array:
    """(B, H, Lq, D) x (B, Hkv, Lk, D)^2 -> (B, H, Lq, D), GQA-aware.

    Numerically equivalent to nldpe_attention (same quantization grids at
    every ACAM crossing) but the score matrix is streamed in KV blocks.
    """
    if use_ref:
        return logdomain_flash_ref(q, k, v, cfg, causal=causal)
    d = q.shape[-1]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    # crossbar outputs pass through log ACAMs (fused Linear->log activation)
    q_l = log_quantize(q.astype(jnp.float32) * scale, cfg)
    k_l = log_quantize(k.astype(jnp.float32), cfg)
    v_l = log_quantize(v.astype(jnp.float32), cfg)
    lq, lk = q.shape[2], k.shape[2]
    out = logdomain_flash_kernel(q_l, k_l, v_l, causal=causal, bits=cfg.bits,
                                 score_range=cfg.score_range,
                                 bq=divisor_block(lq, bq),
                                 bk=divisor_block(lk, bk),
                                 interpret=interpret)
    return out.astype(q.dtype)
