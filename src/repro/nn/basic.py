"""Linear / embedding / norms / rotary — the building blocks.

Compute dtype is the caller's activation dtype (bf16 in production paths);
params live in the dtype set via ``module.param_dtype`` (f32 masters for
training, bf16 for serving dry-runs).  NL-DPE integration: ``linear_apply``
optionally routes through the quantized crossbar path, and activations are
dispatched via NLDPEConfig in the blocks that use them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .module import param


# -- linear -----------------------------------------------------------------

def linear_init(key, d_in: int, d_out: int, bias: bool = False,
                axes: tuple = ("embed", "mlp"), scale: float | None = None):
    p = {"w": param(key, (d_in, d_out), axes, scale=scale)}
    if bias:
        p["b"] = param(key, (d_out,), (axes[1],), init="zeros")
    return p


def linear_apply(p, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# -- embedding ----------------------------------------------------------------

def embedding_init(key, vocab: int, d: int, axes=("vocab", "embed")):
    return {"table": param(key, (vocab, d), axes, init="embed", scale=0.02)}


def embedding_apply(p, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0).astype(dtype)


def unembed_apply(p, x: jax.Array) -> jax.Array:
    """Tied readout: logits = x @ E^T, accumulated and returned in f32 (for
    a stable softmax-xent).  The table is read in its own dtype: casting a
    bf16 vocabulary table to f32 would materialize a second, twice-as-large
    copy of it on every call."""
    dt = jnp.promote_types(x.dtype, p["table"].dtype)
    return jnp.einsum("...d,vd->...v", x.astype(dt), p["table"].astype(dt),
                      preferred_element_type=jnp.float32)


# -- norms --------------------------------------------------------------------

def rmsnorm_init(key, d: int):
    return {"scale": param(key, (d,), ("act_embed",), init="ones")}


def rmsnorm_apply(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    h = h * jax.lax.rsqrt(var + eps)
    return (h * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(key, d: int):
    return {"scale": param(key, (d,), ("act_embed",), init="ones"),
            "bias": param(key, (d,), ("act_embed",), init="zeros")}


def layernorm_apply(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * p["scale"] + p["bias"]).astype(x.dtype)


def dyntanh_init(key, d: int):
    """Dynamic Tanh norm-replacement (paper §VII / ref [42]) — ACAM-friendly."""
    return {"alpha": param(key, (1,), (None,), init="ones"),
            "scale": param(key, (d,), ("act_embed",), init="ones"),
            "bias": param(key, (d,), ("act_embed",), init="zeros")}


def dyntanh_apply(p, x: jax.Array) -> jax.Array:
    h = jnp.tanh(p["alpha"].astype(jnp.float32) * x.astype(jnp.float32))
    return (h * p["scale"] + p["bias"]).astype(x.dtype)


# -- rotary -------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jax.Array:
    half = head_dim // 2
    return theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """x: (B, H, S, D) with D even; positions: (B, S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freqs = rope_freqs(d, theta)                          # (half,)
    if positions.ndim == 1:
        positions = positions[None, :]
    angles = positions[:, None, :, None].astype(jnp.float32) * freqs  # (B,1,S,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
