"""The plain reference: a decoder forward pass in float32 ``jax.numpy``.

It imports nothing of the program.  It follows the configuration file:
pre-norm decoder layers (RMSNorm, grouped-query attention with rotary
positions on the two halves of each head, optional q/k/v biases, a SwiGLU
MLP), a final RMSNorm and a readout: the embedding's transpose where the
configuration ties them, else its own ``lm_head`` matrix.  It runs one
whole sequence at a time, with no cache, no paging and no batching, at
``jax.default_matmul_precision("highest")`` so that float32 is float32 on
the TPU too.  Queries go through in blocks so that no (heads, L, L) score
tensor is ever whole.

The same pass with every matrix product's inputs rounded to float8
(e4m3, scaled per row and per column) is the control: the next precision
below the bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512
LOGIT_BLOCK = 256


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, b, lowp):
    """a (..., k) @ b (k, n) in float32, or on float8-rounded inputs."""
    a, b = a.astype(F32), b.astype(F32)
    if lowp:
        a, b = _fp8(a, -1), _fp8(b, 0)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, theta):
    """x (L, H, D): rotate the two halves of every head by position."""
    length, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(length, dtype=F32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, lowp):
    """Causal softmax attention, q (L, H, D), k/v (L, H, D)."""
    length, h, d = q.shape
    nb = length // Q_BLOCK
    kpos = jnp.arange(length)
    if lowp:
        k, v = _fp8(k, -1), _fp8(v, 1)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        if lowp:
            qb = _fp8(qb, -1)
        s = jnp.einsum("qhd,khd->hqk", qb, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if lowp:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=jax.lax.Precision.HIGHEST)

    return jax.lax.map(block, jnp.arange(nb)).reshape(length, h, d)


def _hidden(w, cfg, tokens, lowp):
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    length = tokens.shape[0]
    x = w["embed"]["table"][tokens].astype(F32)

    def layer(x, lw):
        a = lw["attn"]
        h = _rms(x, lw["norm1"]["scale"], eps)
        q = _mm(h, a["wq"].reshape(d, hq * hd), lowp).reshape(length, hq, hd)
        k = _mm(h, a["wk"].reshape(d, hkv * hd), lowp).reshape(length, hkv, hd)
        v = _mm(h, a["wv"].reshape(d, hkv * hd), lowp).reshape(length, hkv, hd)
        if "bq" in a:
            q = q + a["bq"].astype(F32)
            k = k + a["bk"].astype(F32)
            v = v + a["bv"].astype(F32)
        q, k = _rope(q, theta), _rope(k, theta)
        rep = hq // hkv
        o = _attention(q, jnp.repeat(k, rep, axis=1),
                       jnp.repeat(v, rep, axis=1), lowp)
        x = x + _mm(o.reshape(length, hq * hd),
                    a["wo"].reshape(hq * hd, d), lowp)
        f = lw["ffn"]
        h = _rms(x, lw["norm2"]["scale"], eps)
        g = jax.nn.silu(_mm(h, f["gate"], lowp)) * _mm(h, f["up"], lowp)
        return x + _mm(g, f["down"], lowp), None

    x, _ = jax.lax.scan(layer, x, w["groups"]["b0"])
    return _rms(x, w["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("cfg_items", "lowp"))
def _logit_stats(w, tokens, cand, *, cfg_items, lowp):
    """Per position: the float32 logit gap of ``cand`` below the best
    logit, and the argmax, of the pass in the given precision."""
    cfg = dict(cfg_items)
    x = _hidden(w, cfg, tokens, lowp)
    readout = (w["lm_head"]["w"] if "lm_head" in w
               else w["embed"]["table"].T)
    nb = x.shape[0] // LOGIT_BLOCK

    def block(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * LOGIT_BLOCK, LOGIT_BLOCK)
        cb = jax.lax.dynamic_slice_in_dim(cand, i * LOGIT_BLOCK, LOGIT_BLOCK)
        lg = _mm(xb, readout, lowp)
        got = jnp.take_along_axis(lg, cb[:, None], axis=1)[:, 0]
        return jnp.max(lg, -1) - got, jnp.argmax(lg, -1).astype(jnp.int32)

    gap, top = jax.lax.map(block, jnp.arange(nb))
    return gap.reshape(-1), top.reshape(-1)


def _cfg_items(cfg: dict) -> tuple:
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, cfg.get(k)) for k in keys)


def _padded(seq, length):
    if len(seq) > length:
        raise ValueError(f"sequence of {len(seq)} tokens > {length}")
    out = np.zeros((length,), np.int32)
    out[:len(seq)] = seq
    return out


def served_gaps(w, cfg: dict, length: int, prompt, served,
                lowp: bool = False) -> np.ndarray:
    """For each served token, how far its float32 reference logit lies
    below the reference's best logit at that position (0 where the
    reference would have chosen it too).

    ``length`` is the padded sequence length (the engine's max_len, a
    multiple of the query and logit blocks): one compiled program per
    configuration.  With ``lowp`` it returns the float8 control's gaps
    instead: at each position, the float32 gap of the token that the
    float8 pass puts first.
    """
    prompt, served = list(prompt), list(served)
    seq = prompt + served
    tokens = _padded(seq[:-1], length)
    first = len(prompt) - 1
    n = len(served)
    items = _cfg_items(cfg)
    if not lowp:
        cand = _padded(seq[1:], length)
        gap, _ = _logit_stats(w, tokens, cand, cfg_items=items, lowp=False)
        return np.asarray(gap)[first:first + n]
    _, top = _logit_stats(w, tokens, np.zeros((length,), np.int32),
                          cfg_items=items, lowp=True)
    gap, _ = _logit_stats(w, tokens, np.asarray(top), cfg_items=items,
                          lowp=False)
    return np.asarray(gap)[first:first + n]
