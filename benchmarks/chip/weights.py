"""Random weights drawn from the seed, made by the benchmark.

The program under test and the plain reference are both handed these
arrays, so the reference takes nothing that the program made.  The tree
follows the layout the program's decoder reads (``embed``, the layer
stack under ``groups/b0``, ``final_norm``); the harness checks it against
the program's own parameter shapes before serving.

Scales keep activations and logits of order one at any width: every
projection is N(0, 1/fan_in), the tied embedding N(0, 1/hidden) so that a
logit is N(0, 1), norm scales are 1 + N(0, 0.1) and attention biases
N(0, 0.1), so that no part of the block is an identity.
"""
from __future__ import annotations

import numpy as np


def key_words(seed: int) -> np.ndarray:
    """Two uint32 words of key data from any whole-number seed (JAX's
    integer seeds keep only 32 bits)."""
    ss = np.random.SeedSequence(seed & (2**64 - 1))
    return ss.generate_state(2, np.uint32)


def shapes(cfg: dict) -> dict:
    """name path -> (shape, init, scale) of every weight."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // hq
    n = cfg["num_hidden_layers"]
    out = {
        ("embed", "table"): ((v, d), "normal", d ** -0.5),
        ("final_norm", "scale"): ((d,), "scale", 0.1),
    }
    blk = {
        ("norm1", "scale"): ((d,), "scale", 0.1),
        ("norm2", "scale"): ((d,), "scale", 0.1),
        ("attn", "wq"): ((d, hq, hd), "normal", d ** -0.5),
        ("attn", "wk"): ((d, hkv, hd), "normal", d ** -0.5),
        ("attn", "wv"): ((d, hkv, hd), "normal", d ** -0.5),
        ("attn", "wo"): ((hq, hd, d), "normal", (hq * hd) ** -0.5),
        ("ffn", "up"): ((d, f), "normal", d ** -0.5),
        ("ffn", "gate"): ((d, f), "normal", d ** -0.5),
        ("ffn", "down"): ((f, d), "normal", f ** -0.5),
    }
    if cfg.get("attention_bias"):
        blk.update({("attn", "bq"): ((hq, hd), "normal", 0.1),
                    ("attn", "bk"): ((hkv, hd), "normal", 0.1),
                    ("attn", "bv"): ((hkv, hd), "normal", 0.1)})
    for path, (shape, init, scale) in blk.items():
        out[("groups", "b0") + path] = ((n,) + shape, init, scale)
    return out


def make(cfg: dict, seed: int, dtype):
    """All weights in ``dtype``, made on the default device by one jitted
    call from the seed."""
    import jax
    import jax.numpy as jnp

    spec = shapes(cfg)

    def build(key):
        tree: dict = {}
        for i, (path, (shape, init, scale)) in enumerate(sorted(spec.items())):
            x = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32) * scale
            if init == "scale":
                x = 1.0 + x
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            node[path[-1]] = x.astype(dtype)
        return tree

    key = jax.random.wrap_key_data(jnp.asarray(key_words(seed)))
    return jax.jit(build)(key)
