"""AOT compiles of the serving path's Pallas kernels for a TPU v5e.

The Pallas interpreter never checks TPU tiling, VMEM budgets or Mosaic's
vector layouts, so every interpret-mode test passes for a kernel the chip's
compiler refuses.  These cases compile each kernel at Qwen2.5-3B widths
(d_model 2048, 16 query / 2 KV heads of 128, d_ff 11008, 16-token pages)
for a *described* v5e chip — the TPU compiler ships with jaxlib, no chip
is needed — and assert that the compiled program holds the Mosaic kernel
(``tpu_custom_call``) rather than an interpreted lowering.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this module.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.dual_compute.ops import (fused_linear_acam,
                                            logdomain_flash_attention)
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.paged_attention.ops import paged_attention

# Qwen2.5-3B attention/MLP widths and the serving engine's page geometry
D_MODEL, D_FF, HQ, HKV, HEAD = 2048, 11008, 16, 2, 128
SLOTS, PAGE, MAX_LEN = 8, 16, 1024
NB = MAX_LEN // PAGE
PAGES = SLOTS * NB


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler plugin in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _paged(kv_quant, q_len):
    q = (SLOTS, HQ, HEAD) if q_len == 1 else (SLOTS, HQ, q_len, HEAD)
    pool = (PAGES, HKV, PAGE, HEAD)
    shapes = [(q, jnp.float32),
              (pool, jnp.int8 if kv_quant else jnp.bfloat16),
              (pool, jnp.int8 if kv_quant else jnp.bfloat16),
              ((SLOTS, NB), jnp.int32), ((SLOTS,), jnp.int32)]
    if kv_quant:
        shapes += [(pool[:3], jnp.float32), (pool[:3], jnp.float32)]

    def fn(q, kp, vp, bt, lengths, ks=None, vs=None):
        return paged_attention(q, kp, vp, bt, lengths, k_scale=ks,
                               v_scale=vs, kv_quant=kv_quant,
                               interpret=False)
    return fn, shapes


@pytest.mark.parametrize("kv_quant,q_len", [(None, 1), (None, 3),
                                            ("log8", 1)],
                         ids=["decode", "verify_q3", "log8_decode"])
def test_paged_attention_compiles(one_chip, kv_quant, q_len):
    fn, shapes = _paged(kv_quant, q_len)
    _compile(fn, one_chip, *shapes)


# the benchmark cells' engines: (slots, blocks, pages, q heads, kv heads,
# head_dim) — Qwen2.5-3B (G 8) and MiniCPM-2B (MHA, G 1, head_dim 64)
CELL_SHAPES = {"qwen2_5_3b": (32, 256, 7168, HQ, HKV, HEAD),
               "minicpm_2b": (8, 192, 1536, 36, 36, 64)}


@pytest.mark.parametrize("q_len", [1, 16], ids=["decode", "chunk16"])
@pytest.mark.parametrize("model", sorted(CELL_SHAPES))
def test_paged_attention_compiles_at_cell_shapes(one_chip, model, q_len):
    """The kernel the paged engine runs on a TPU, at the benchmark cells'
    engine shapes: bf16 pools of 16-token pages, decode and a 16-row
    chunk."""
    slots, nb, pages, hq, hkv, head = CELL_SHAPES[model]
    q = (slots, hq, head) if q_len == 1 else (slots, hq, q_len, head)
    pool = (pages, hkv, PAGE, head)
    _compile(functools.partial(paged_attention, interpret=False), one_chip,
             (q, jnp.bfloat16), (pool, jnp.bfloat16), (pool, jnp.bfloat16),
             ((slots, nb), jnp.int32), ((slots,), jnp.int32))


def test_fused_linear_acam_compiles(one_chip):
    fn = functools.partial(fused_linear_acam, act="silu", interpret=False)
    _compile(fn, one_chip, ((16, D_MODEL), jnp.bfloat16),
             ((D_MODEL, D_FF), jnp.bfloat16))


@pytest.mark.parametrize("attn", ["logdomain", "flash"])
def test_flash_attention_compiles(one_chip, attn):
    fn = (logdomain_flash_attention if attn == "logdomain"
          else flash_attention)
    _compile(functools.partial(fn, interpret=False), one_chip,
             ((1, HQ, 256, HEAD), jnp.bfloat16),
             ((1, HKV, 256, HEAD), jnp.bfloat16),
             ((1, HKV, 256, HEAD), jnp.bfloat16))
