"""Cross-kernel conformance suite: ONE harness for every Pallas kernel.

Each kernel subpackage ships a pure-jnp oracle (``ref.py``, reachable via
``use_ref=True`` on the public op).  Historically every kernel had its own
ad-hoc shape grid; this suite drives all of them through a single
parametrized matrix:

* dtypes        — float32 and bfloat16 inputs,
* shapes        — MXU-aligned, odd, and non-tile-aligned (the padding and
                  divisor-block fallbacks are exactly where kernels rot),
* batch/groups  — leading batch extents and GQA query-group ratios.

A kernel is conformant when the Pallas path (interpret mode on CPU)
matches its oracle within the per-dtype tolerance.  Quantizing kernels
(ACAM, fused dual-compute) additionally get one output-grid code step of
slack where the two paths order float reductions differently.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dt
from repro.core.crossbar import program_linear
from repro.core.logdomain import DEFAULT_CFG
from repro.kernels.acam_activation.ops import acam_apply
from repro.kernels.crossbar_vmm.ops import crossbar_matmul
from repro.kernels.dual_compute.ops import (fused_crossbar_acam,
                                            logdomain_flash_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.nldpe_qmatmul.ops import nldpe_matmul_int8
from repro.kernels.paged_attention.ops import paged_attention
from repro.nn.attention import _quantize_kv

RNG = np.random.default_rng(2024)

F32_TOL = dict(rtol=1e-4, atol=1e-4)
BF16_TOL = dict(rtol=0.05, atol=0.05)

# (M, K, N): aligned / odd / non-tile-aligned / degenerate-row
MATMUL_SHAPES = [(128, 128, 128), (8, 16, 8), (33, 65, 17), (1, 300, 5)]
# (B, Hq, Hkv, Lq, Lk, D): group = Hq/Hkv in {1, 2, 4}; odd lengths included
ATTN_SHAPES = [(1, 2, 2, 16, 16, 8), (2, 4, 2, 32, 32, 16),
               (1, 4, 1, 8, 40, 32), (1, 2, 2, 1, 24, 16),
               (2, 2, 1, 12, 20, 8)]
# arbitrary activation tensor shapes incl. scalar-ish and 3-d batch groups
ACT_SHAPES = [(7,), (3, 40), (2, 5, 17), (260,), (4, 2, 2, 9)]
# (B, Hq, Hkv, P, NB, ps, D): GQA groups in {1, 2, 4}, odd page sizes,
# ragged lengths incl. a sequence shorter than one page
PAGED_SHAPES = [(1, 2, 2, 8, 2, 8, 8), (2, 4, 2, 12, 3, 16, 16),
                (1, 4, 1, 9, 3, 6, 32), (2, 2, 1, 10, 4, 5, 8),
                (1, 8, 2, 6, 2, 128, 64)]
# (B, Hq, Hkv, P, NB, ps, D, q_len): the speculative-verify grid — q_len
# queries per sequence with the per-row ragged staircase (query j attends
# to lengths[b] + j positions); q_len spanning a page boundary included
PAGED_MQ_SHAPES = [(1, 2, 2, 8, 2, 8, 8, 2), (2, 4, 2, 12, 3, 16, 16, 3),
                   (1, 4, 1, 9, 3, 6, 32, 5), (2, 2, 1, 10, 4, 5, 8, 7)]
# (B, Hq, Hkv, P, NB, ps, D, q_len): quantized pools (int8 codes + scales,
# dequant inside the kernel grid) — decode (q_len 1) and the ragged
# chunk-prefill staircase, odd page sizes x GQA groups (DESIGN.md §11)
PAGED_QUANT_SHAPES = [(1, 2, 2, 8, 2, 8, 8, 1), (2, 4, 2, 12, 3, 16, 16, 3),
                      (1, 4, 1, 9, 3, 6, 32, 5), (2, 2, 1, 10, 4, 5, 8, 2)]


def _rand(shape, dtype, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32) * scale,
                       dtype)


def _tol(dtype):
    return F32_TOL if dtype == jnp.float32 else BF16_TOL


@dataclasses.dataclass(frozen=True)
class Case:
    """One kernel-vs-oracle evaluation: run() -> (kernel_out, ref_out,
    extra atol for quantized-output grids)."""

    kernel: str
    shape: tuple
    run: object

    @property
    def id(self) -> str:
        return f"{self.kernel}-{'x'.join(map(str, self.shape))}"


def _crossbar_case(shape):
    def run(dtype):
        m, k, n = shape
        w = _rand((k, n), jnp.float32, 0.1)
        x = _rand((m, k), dtype)
        plan, _ = program_linear(w)
        return (crossbar_matmul(x, plan),
                crossbar_matmul(x, plan, use_ref=True), 0.0)
    return Case("crossbar_vmm", shape, run)


def _qmatmul_case(shape):
    def run(dtype):
        m, k, n = shape
        a = _rand((m, k), dtype)
        b = _rand((k, n), dtype)
        return (nldpe_matmul_int8(a, b),
                nldpe_matmul_int8(a, b, use_ref=True), 0.0)
    return Case("nldpe_qmatmul", shape, run)


def _acam_case(shape, fn="gelu"):
    def run(dtype):
        t = dt.build_table(fn)
        x = jnp.asarray(
            RNG.uniform(*t.in_domain, size=shape).astype(np.float32), dtype)
        # both paths quantize to the same output grid; a float tie at an
        # interval edge may flip one code
        return acam_apply(x, t), acam_apply(x, t, use_ref=True), t.out_spec.step
    return Case("acam_activation", shape, run)


def _dual_compute_case(shape, fn="sigmoid"):
    def run(dtype):
        m, k, n = shape
        t = dt.build_table(fn)
        w = _rand((k, n), jnp.float32, 0.1)
        x = _rand((m, k), dtype)
        plan, _ = program_linear(w)
        return (fused_crossbar_acam(x, plan, t),
                fused_crossbar_acam(x, plan, t, use_ref=True), t.out_spec.step)
    return Case("dual_compute", shape, run)


def _flash_case(shape):
    def run(dtype):
        b, hq, hkv, lq, lk, d = shape
        q = _rand((b, hq, lq, d), dtype)
        k = _rand((b, hkv, lk, d), dtype)
        v = _rand((b, hkv, lk, d), dtype)
        return (flash_attention(q, k, v, bq=8, bk=8),
                flash_attention(q, k, v, use_ref=True), 0.0)
    return Case("flash_attention", shape, run)


def _logdomain_flash_case(shape):
    exp_lsb = 1.0 / ((1 << DEFAULT_CFG.bits) - 1)

    def run(dtype):
        b, hq, hkv, lq, lk, d = shape
        q = _rand((b, hq, lq, d), dtype)
        k = _rand((b, hkv, lk, d), dtype)
        v = _rand((b, hkv, lk, d), dtype)
        # the production wrapper upcasts to f32 before the 1/sqrt(d) scale;
        # hand the oracle the upcast inputs so both paths hit the log-grid
        # code boundaries at the same precision
        qf, kf, vf = (t.astype(jnp.float32) for t in (q, k, v))
        return (logdomain_flash_attention(q, k, v, bq=8, bk=8),
                logdomain_flash_attention(qf, kf, vf, use_ref=True), exp_lsb)
    return Case("logdomain_flash", shape, run)


def _paged_case(shape):
    def run(dtype):
        b, hq, hkv, p, nb, ps, d = shape
        q = _rand((b, hq, d), dtype)
        kp = _rand((p, hkv, ps, d), dtype)
        vp = _rand((p, hkv, ps, d), dtype)
        bt = jnp.asarray(RNG.integers(0, p, size=(b, nb)), jnp.int32)
        lengths = jnp.asarray(RNG.integers(1, nb * ps + 1, size=(b,)),
                              jnp.int32)
        return (paged_attention(q, kp, vp, bt, lengths),
                paged_attention(q, kp, vp, bt, lengths, use_ref=True), 0.0)
    return Case("paged_attention", shape, run)


def _paged_mq_case(shape):
    def run(dtype):
        b, hq, hkv, p, nb, ps, d, ql = shape
        q = _rand((b, hq, ql, d), dtype)
        kp = _rand((p, hkv, ps, d), dtype)
        vp = _rand((p, hkv, ps, d), dtype)
        bt = jnp.asarray(RNG.integers(0, p, size=(b, nb)), jnp.int32)
        # leave room for the staircase: lengths[b] + ql - 1 <= NB*ps
        lengths = jnp.asarray(
            RNG.integers(1, nb * ps - ql + 2, size=(b,)), jnp.int32)
        return (paged_attention(q, kp, vp, bt, lengths),
                paged_attention(q, kp, vp, bt, lengths, use_ref=True), 0.0)
    return Case("paged_attention_mq", shape, run)


def _paged_quant_case(shape, mode):
    def run(dtype):
        b, hq, hkv, p, nb, ps, d, ql = shape
        q = _rand((b, hq, ql, d), dtype)
        kq, ks = _quantize_kv(_rand((p, hkv, ps, d), jnp.float32), mode)
        vq, vs = _quantize_kv(_rand((p, hkv, ps, d), jnp.float32), mode)
        bt = jnp.asarray(RNG.integers(0, p, size=(b, nb)), jnp.int32)
        lengths = jnp.asarray(
            RNG.integers(1, nb * ps - ql + 2, size=(b,)), jnp.int32)
        kw = dict(k_scale=ks, v_scale=vs, kv_quant=mode)
        # both paths dequantize the SAME codes through kv_decode, so this
        # row checks the in-kernel dequant, not the quantization error
        return (paged_attention(q, kq, vq, bt, lengths, **kw),
                paged_attention(q, kq, vq, bt, lengths, use_ref=True, **kw),
                0.0)
    return Case(f"paged_{mode}", shape, run)


CASES = (
    [_crossbar_case(s) for s in MATMUL_SHAPES]
    + [_qmatmul_case(s) for s in MATMUL_SHAPES]
    + [_acam_case(s) for s in ACT_SHAPES]
    + [_dual_compute_case(s) for s in MATMUL_SHAPES]
    + [_flash_case(s) for s in ATTN_SHAPES]
    + [_logdomain_flash_case(s) for s in ATTN_SHAPES]
    + [_paged_case(s) for s in PAGED_SHAPES]
    + [_paged_mq_case(s) for s in PAGED_MQ_SHAPES]
    + [_paged_quant_case(s, m) for s in PAGED_QUANT_SHAPES
       for m in ("log8", "int8")]
)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES, ids=[c.id for c in CASES])
def test_kernel_matches_reference(case, dtype):
    out_k, out_r, grid_step = case.run(dtype)
    assert out_k.shape == out_r.shape, case.id
    tol = dict(_tol(dtype))
    tol["atol"] = tol["atol"] + grid_step
    np.testing.assert_allclose(np.asarray(out_k, np.float32),
                               np.asarray(out_r, np.float32), **tol)


@pytest.mark.parametrize("case", CASES[:1] + CASES[len(MATMUL_SHAPES):
                                                   len(MATMUL_SHAPES) + 1],
                         ids=lambda c: c.id)
def test_kernel_output_dtype_is_stable(case):
    """Kernels may compute in f32 internally but must not change the
    result's floatness: outputs stay a real floating dtype."""
    out_k, out_r, _ = case.run(jnp.float32)
    assert jnp.issubdtype(out_k.dtype, jnp.floating)
    assert jnp.issubdtype(out_r.dtype, jnp.floating)


def test_paged_attention_sentinel_blocks_do_not_alias():
    """A ``lengths`` overrun past the allocated blocks (e.g. a spec-verify
    slack budgeting bug) must read NOTHING through unmapped block-table
    entries.  The old wrapper clamped the sentinel (``num_pages``) onto the
    last real page, silently attending to another slot's data; now the
    gather clamps only the DMA index and the softmax masks the whole page
    (the read-side mirror of the write path's OOB-drop scatter)."""
    rng = np.random.default_rng(7)
    b, hq, hkv, p, nb, ps, d = 2, 4, 2, 6, 3, 5, 16
    q = jnp.asarray(rng.normal(size=(b, hq, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(p, hkv, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(p, hkv, ps, d)), jnp.float32)
    # slot 0 fully mapped; slot 1 has only 2 of 3 blocks — its last entry
    # holds the unmapped sentinel (and pages 4/5 belong to slot 0 alone)
    bt = jnp.asarray([[3, 4, 5], [0, 1, p]], jnp.int32)
    over = jnp.asarray([nb * ps, nb * ps], jnp.int32)   # overruns slot 1
    clip = jnp.asarray([nb * ps, 2 * ps], jnp.int32)    # exact mapped extent

    for use_ref in (False, True):
        o_over = paged_attention(q, kp, vp, bt, over, use_ref=use_ref)
        o_clip = paged_attention(q, kp, vp, bt, clip, use_ref=use_ref)
        # the overrun only ever covers sentinel positions -> identical to
        # the exactly-clipped lengths
        np.testing.assert_allclose(np.asarray(o_over), np.asarray(o_clip),
                                   rtol=1e-6, atol=1e-6)
        # poison the last real page (what the old clamp aliased the
        # sentinel onto): slot 1 must not see it at all
        o_poison = paged_attention(q, kp.at[p - 1].add(100.0),
                                   vp.at[p - 1].add(100.0), bt, over,
                                   use_ref=use_ref)
        np.testing.assert_array_equal(np.asarray(o_poison[1]),
                                      np.asarray(o_over[1]))
        # ...while slot 0 (which owns page 5) must
        assert not np.allclose(np.asarray(o_poison[0]), np.asarray(o_over[0]))
    # negative entries are sentinels too (never-allocated table rows)
    btn = bt.at[1, 2].set(-1)
    for use_ref in (False, True):
        o_neg = paged_attention(q, kp, vp, btn, over, use_ref=use_ref)
        o_clip = paged_attention(q, kp, vp, btn, clip, use_ref=use_ref)
        np.testing.assert_allclose(np.asarray(o_neg), np.asarray(o_clip),
                                   rtol=1e-6, atol=1e-6)


def test_paged_attention_sharded_conformance():
    """``paged_attention_sharded`` (the shard_map dispatch the serve
    engines use under a mesh, ISSUE 5) vs the dense-view reference, on a
    forced 8-device host platform: decode and q_len>1 verify grids, odd
    page sizes x GQA groups.  On the (1, 2) mesh most shapes genuinely
    shard heads; on (2, 4) the kv-head counts do NOT divide model=4, so
    the wrapper's divisibility fallback must replicate — never crash or
    diverge.  Runs in a subprocess because the device-count flag must be
    set before jax initializes (same pattern as tests/test_distributed)."""
    import os
    import subprocess
    import sys
    import textwrap

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(repo, "src")
    script = textwrap.dedent(f"""
        import numpy as np
        import jax.numpy as jnp
        from repro.kernels.paged_attention.ops import (
            paged_attention, paged_attention_sharded)
        from repro.launch.mesh import make_mesh
        from repro.parallel.sharding import serve_exact_rules

        rng = np.random.default_rng(2024)
        rules = serve_exact_rules()
        meshes = [make_mesh(s, ("data", "model")) for s in [(1, 2), (2, 4)]]
        # (B, Hq, Hkv, P, NB, ps, D, q_len): q_len == 1 -> the 3-d decode
        # signature; > 1 -> the speculative-verify staircase grid
        for shape in {PAGED_SHAPES!r}:
            for ql in (1, 3):
                b, hq, hkv, p, nb, ps, d = shape
                q = jnp.asarray(rng.normal(size=(b, hq, ql, d)),
                                jnp.float32)
                if ql == 1:
                    q = q[:, :, 0]                 # decode signature
                kp = jnp.asarray(rng.normal(size=(p, hkv, ps, d)),
                                 jnp.float32)
                vp = jnp.asarray(rng.normal(size=(p, hkv, ps, d)),
                                 jnp.float32)
                bt = jnp.asarray(rng.integers(0, p, size=(b, nb)), jnp.int32)
                lengths = jnp.asarray(
                    rng.integers(1, nb * ps - ql + 2, size=(b,)), jnp.int32)
                ref = paged_attention(q, kp, vp, bt, lengths, use_ref=True)
                for mesh in meshes:
                    out = paged_attention_sharded(q, kp, vp, bt, lengths,
                                                  mesh, rules)
                    assert out.shape == ref.shape, (shape, ql, mesh.shape)
                    np.testing.assert_allclose(
                        np.asarray(out), np.asarray(ref),
                        rtol=1e-4, atol=1e-4,
                        err_msg=f"{{shape}} ql={{ql}} mesh={{mesh.shape}}")
                print("ok", shape, "ql", ql, flush=True)
        print("SHARDED-CONFORMANT")
    """)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-4000:]
    assert "SHARDED-CONFORMANT" in out.stdout
