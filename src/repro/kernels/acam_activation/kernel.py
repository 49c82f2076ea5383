"""Pallas TPU kernel: 8-bit ACAM activation (interval match -> Gray decode).

Hardware-faithful simulation of one ACAM unit (paper Fig 4(e)): for every
element x, each output bit i is OR over rows r of (lo[i,r] <= x <= hi[i,r]);
the Gray bit-planes are XOR-decoded and the binary code dequantized.

TPU mapping: this is pure VPU work.  Elements are processed in
(block_rows, 128)-shaped VMEM tiles (lane dimension 128-aligned); the
threshold table (bits, rows) is tiny (<= 8 x 128 floats = 4 KB) and sits
in SMEM, so every compare is a 2-D tile against one scalar threshold,
looped over the table's rows with one running OR per bit plane.  The Gray
decode is an unrolled 8-step mod-2 cumulative sum (XOR chain of Fig 4(e)).

VMEM budget per grid step (defaults, f32): x tile 8*128*4 = 4 KB, out
4 KB, eight bit-plane accumulators 32 KB -> well under the ~16 MB VMEM of
a TPU core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret


def acam_decode_tile(x, lo_ref, hi_ref, bits: int, out_lo: float,
                     out_step: float):
    """Interval match + Gray decode of one VMEM tile.

    x: (bm, bn) value; lo_ref/hi_ref: (bits, rows) threshold refs in SMEM.
    Bit plane ``i`` is the OR over rows ``r`` of ``lo[i, r] <= x <=
    hi[i, r]``: each compare is 2-D against a scalar (Mosaic has no layout
    for a (bm, bn, bits, rows) broadcast), so callers keep bm small enough
    for the ``bits`` plane accumulators to stay in vregs.  Shared by this
    kernel and kernels/dual_compute so the two stay bit-identical.
    """
    def row(r, planes):
        return tuple(
            jnp.maximum(g, ((x >= lo_ref[i, r]) & (x <= hi_ref[i, r]))
                        .astype(jnp.float32))
            for i, g in enumerate(planes))

    g = jax.lax.fori_loop(0, lo_ref.shape[1], row,
                          (jnp.zeros(x.shape, jnp.float32),) * bits)
    # XOR decode: b_i = XOR(g_{n-1}..g_i)  == reverse cumulative mod-2 sum
    code = jnp.zeros(x.shape, jnp.float32)
    b = jnp.zeros(x.shape, jnp.float32)
    for i in range(bits - 1, -1, -1):
        b = jnp.abs(b - g[i])                          # XOR on {0,1} floats
        code = code + b * (2.0 ** i)
    return code * out_step + out_lo


def _acam_kernel(x_ref, lo_ref, hi_ref, o_ref, *, bits: int,
                 out_lo: float, out_step: float):
    o_ref[...] = acam_decode_tile(x_ref[...], lo_ref, hi_ref,
                                  bits, out_lo, out_step)


@functools.partial(jax.jit, static_argnames=("bits", "out_lo", "out_step",
                                             "block_rows", "interpret"))
def acam_activation_kernel(x: jax.Array, lo: jax.Array, hi: jax.Array,
                           bits: int = 8, out_lo: float = 0.0,
                           out_step: float = 1.0, block_rows: int = 8,
                           interpret: bool | None = None) -> jax.Array:
    """x: (R, 128k) f32 2-D (callers flatten/pad), lo/hi: (bits, rows)."""
    r, c = x.shape
    assert r % block_rows == 0, (r, block_rows)
    table_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_acam_kernel, bits=bits, out_lo=out_lo,
                          out_step=out_step),
        grid=(r // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
                  table_spec, table_spec],
        out_specs=pl.BlockSpec((block_rows, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x, lo, hi)
