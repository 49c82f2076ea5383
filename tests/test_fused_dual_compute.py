"""Fused ADC-free dual-compute pipeline: fusion equivalence + serve loop.

The fused kernels must be *numerically faithful* to the two-kernel oracles
they replace (same quantization grids at every ACAM crossing), and the
scanned decode loop must generate the exact same tokens as the seed
per-token Python loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dt
from repro.core.acam import acam_activation
from repro.core.crossbar import program_linear
from repro.core.engine import FUSED, ON
from repro.core.logdomain import DEFAULT_CFG
from repro.kernels import resolve_interpret
from repro.kernels.acam_activation.ops import acam_apply
from repro.kernels.crossbar_vmm.ops import crossbar_matmul
from repro.kernels.dual_compute.ops import (fused_crossbar_acam,
                                            fused_linear_acam,
                                            logdomain_flash_attention)

RNG = np.random.default_rng(7)

EXP_LSB = 1.0 / ((1 << DEFAULT_CFG.bits) - 1)   # one exp-output-grid LSB


# ---------------------------------------------------------------------------
# crossbar -> ACAM fusion
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 32, 16), (33, 96, 80), (128, 128, 128),
                                   (1, 300, 5)])
@pytest.mark.parametrize("fn", ["gelu", "sigmoid"])
def test_fused_crossbar_acam_matches_two_kernel_oracle(m, k, n, fn):
    t = dt.build_table(fn)
    w = jnp.asarray(RNG.normal(size=(k, n)).astype(np.float32) * 0.1)
    x = jnp.asarray(RNG.normal(size=(m, k)).astype(np.float32))
    plan, _ = program_linear(w)
    y_fused = fused_crossbar_acam(x, plan, t)
    y_two = acam_apply(crossbar_matmul(x, plan), t)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_two),
                               atol=1e-5)


def test_fused_crossbar_acam_matches_pure_ref():
    t = dt.build_table("relu")
    w = jnp.asarray(RNG.normal(size=(64, 48)).astype(np.float32) * 0.1)
    x = jnp.asarray(RNG.normal(size=(16, 64)).astype(np.float32))
    plan, _ = program_linear(w)
    y_k = fused_crossbar_acam(x, plan, t)
    y_r = fused_crossbar_acam(x, plan, t, use_ref=True)
    # ref matmul order differs; a float-level tie near an interval edge can
    # flip one output code, so allow one code step
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               atol=t.out_spec.step + 1e-5)


def test_fused_crossbar_acam_noisy_draw_matches():
    t = dt.build_table("gelu")
    w = jnp.asarray(RNG.normal(size=(40, 24)).astype(np.float32) * 0.1)
    x = jnp.asarray(RNG.normal(size=(6, 40)).astype(np.float32))
    plan, _ = program_linear(w)
    key = jax.random.key(3)
    y_fused = fused_crossbar_acam(x, plan, t, rng=key)
    y_two = acam_apply(crossbar_matmul(x, plan, rng=key), t)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_two),
                               atol=1e-5)


def test_fused_linear_acam_matches_piecewise_path():
    """Model-level fused Linear+act == matmul -> piecewise ACAM fast path."""
    t = dt.build_table("silu")
    w = jnp.asarray(RNG.normal(size=(72, 56)).astype(np.float32) * 0.2)
    x = jnp.asarray(RNG.normal(size=(3, 9, 72)).astype(np.float32))
    y_fused = fused_linear_acam(x, w, "silu")
    y_two = acam_activation(x @ w, "silu")
    assert y_fused.shape == y_two.shape == (3, 9, 56)
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(y_two),
                               atol=t.out_spec.step + 1e-5)


# ---------------------------------------------------------------------------
# log-domain flash attention (Fig 6c exp-bypass, streamed)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,lq,lk,d", [
    (1, 2, 2, 16, 16, 8),        # MHA square
    (2, 4, 2, 24, 24, 16),       # GQA
    (1, 4, 1, 8, 40, 16),        # MQA, queries at the end
    (1, 2, 2, 1, 40, 16),        # single-query decode
])
def test_logdomain_flash_matches_nldpe_attention(b, hq, hkv, lq, lk, d):
    q = jnp.asarray(RNG.normal(size=(b, hq, lq, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, hkv, lk, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, lk, d)).astype(np.float32))
    o_k = logdomain_flash_attention(q, k, v, bq=8, bk=8)
    o_r = logdomain_flash_attention(q, k, v, use_ref=True)
    assert float(jnp.max(jnp.abs(o_k - o_r))) <= EXP_LSB


def test_logdomain_flash_noncausal():
    q = jnp.asarray(RNG.normal(size=(1, 2, 12, 8)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 2, 20, 8)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 2, 20, 8)).astype(np.float32))
    o_k = logdomain_flash_attention(q, k, v, causal=False, bq=4, bk=4)
    o_r = logdomain_flash_attention(q, k, v, causal=False, use_ref=True)
    assert float(jnp.max(jnp.abs(o_k - o_r))) <= EXP_LSB


def test_engine_dispatches_fused_attention():
    q = jnp.asarray(RNG.normal(size=(1, 2, 16, 8)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 2, 16, 8)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 2, 16, 8)).astype(np.float32))
    o_f = FUSED.attention(q, k, v, causal=True, mask=None)
    o_u = ON.attention(q, k, v, causal=True, mask=None)
    assert float(jnp.max(jnp.abs(o_f - o_u))) <= EXP_LSB


# ---------------------------------------------------------------------------
# model-level equivalence: fused config vs two-kernel config
# ---------------------------------------------------------------------------

def test_mlp_fused_matches_unfused():
    from repro.nn.mlp import mlp_apply, mlp_init

    key = jax.random.key(0)
    p = mlp_init(key, 32, 64, gated=True)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    x = jnp.asarray(RNG.normal(size=(2, 5, 32)).astype(np.float32))
    y_f = mlp_apply(p, x, act="silu", nldpe=FUSED)
    y_u = mlp_apply(p, x, act="silu", nldpe=ON)
    # differences: matmul blocking + interval-match vs piecewise ties; both
    # bounded by one ACAM output step propagated through the down proj
    assert float(jnp.max(jnp.abs(y_f - y_u))) < 0.15
    assert float(jnp.mean(jnp.abs(y_f - y_u))) < 0.01


# ---------------------------------------------------------------------------
# scanned, buffer-donating decode loop
# ---------------------------------------------------------------------------

def test_scanned_generate_matches_python_loop():
    from repro.configs import get_config
    from repro.launch.serve import (build_decode_step, build_generate_fn,
                                    build_prefill_step, python_loop_decode)
    from repro.models import lm
    from repro.nn.module import param_dtype

    cfg = get_config("qwen2_5_3b", reduced=True)
    key = jax.random.key(0)
    with param_dtype(jnp.float32):
        params = lm.init_params(key, cfg)
    batch, prompt_len, gen_len = 2, 8, 6
    prompts = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)
    prefill = jax.jit(build_prefill_step(cfg))

    def fresh():
        cache = lm.init_model_cache(cfg, batch, prompt_len + gen_len,
                                    dtype=jnp.float32)
        logits, cache = prefill(params, cache, prompts)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

    tok0, cache = fresh()
    decode = jax.jit(build_decode_step(cfg))
    gen_py, _ = python_loop_decode(decode, params, cache, tok0, prompt_len,
                                   gen_len)

    tok0, cache = fresh()
    generate = build_generate_fn(cfg, gen_len)
    gen_scan, new_cache = generate(params, cache, tok0, jnp.int32(prompt_len))

    assert gen_scan.shape == (batch, gen_len)
    np.testing.assert_array_equal(np.asarray(gen_py), np.asarray(gen_scan))
    # donated cache: the returned cache is usable for continued decode
    logits, _ = decode(params, new_cache, gen_scan[:, -1],
                       jnp.int32(prompt_len + gen_len - 1))
    assert bool(jnp.all(jnp.isfinite(logits)))


# ---------------------------------------------------------------------------
# satellites: backend-aware interpret + ACAMTable.padded
# ---------------------------------------------------------------------------

def test_resolve_interpret_backend_default():
    explicit_true, explicit_false = resolve_interpret(True), resolve_interpret(False)
    assert explicit_true is True and explicit_false is False
    assert resolve_interpret(None) == (jax.default_backend() == "cpu")


def test_acam_table_padded_up_and_down():
    t = dt.build_table("gelu", bits=8)
    need = max(t.rows_per_bit)
    xs = np.linspace(*t.in_domain, 501)
    from repro.core.acam import eval_table_np

    y0 = eval_table_np(t, xs)
    up = t.padded(t.lo.shape[1] + 13)
    assert up.lo.shape == (t.bits, t.lo.shape[1] + 13)
    np.testing.assert_array_equal(eval_table_np(up, xs), y0)

    down = t.padded(need)          # shrink to the minimum that loses nothing
    assert down.lo.shape == (t.bits, need)
    np.testing.assert_array_equal(eval_table_np(down, xs), y0)

    with pytest.raises(ValueError):
        t.padded(need - 1)
