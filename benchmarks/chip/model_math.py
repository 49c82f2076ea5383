"""Peaks, bytes and FLOPs of the benchmark's configurations.

Everything here is computed from a configuration file's sizes and the
peak table, never from the program.  The roofline and MFU readers use it
so that every implementation of a step is read against the same work.
"""
from __future__ import annotations

import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
BYTES = {"bfloat16": 2, "float32": 4}


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, never a default."""
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"peaks.json has {sorted(table)}")
    return table[device_kind]


def head_dim(cfg: dict) -> int:
    return (cfg.get("head_dim")
            or cfg["hidden_size"] // cfg["num_attention_heads"])


def layer_params(cfg: dict) -> int:
    """Parameters of one decoder layer: attention (with its biases), the
    gated MLP and both RMSNorm scales."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    attn = d * hd * (hq + 2 * hkv) + hq * hd * d
    if cfg.get("attention_bias"):
        attn += hd * (hq + 2 * hkv)
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp + 2 * d


def embed_params(cfg: dict) -> int:
    n = cfg["vocab_size"] * cfg["hidden_size"]
    return n if cfg.get("tie_word_embeddings") else 2 * n


def param_count(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg) + embed_params(cfg)
            + cfg["hidden_size"])


def weight_bytes(cfg: dict) -> int:
    return param_count(cfg) * BYTES[cfg.get("torch_dtype", "bfloat16")]


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one position in every layer, in the weights' dtype."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * BYTES[cfg.get("torch_dtype", "bfloat16")])


def matmul_flops_per_token(cfg: dict) -> int:
    """2 x the non-embedding parameters: every weight of every layer is one
    multiply-add per token."""
    return 2 * cfg["num_hidden_layers"] * layer_params(cfg)


def logits_flops(cfg: dict) -> int:
    """One position's readout against the vocabulary."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def attn_flops_per_position(cfg: dict) -> int:
    """Scores and values of one query against one cached position, summed
    over layers and query heads."""
    return (4 * cfg["num_hidden_layers"] * cfg["num_attention_heads"]
            * head_dim(cfg))


def prefill_flops(cfg: dict, prompt_len: int, reuse: int) -> int:
    """Useful FLOPs of prefilling positions [reuse, prompt_len) and reading
    out the last one: the positions served from the prefix cache are not
    computed, and only the last position's logits are needed."""
    n = prompt_len - reuse
    # position p attends to p + 1 positions
    attended = (prompt_len * (prompt_len + 1) - reuse * (reuse + 1)) // 2
    return (n * matmul_flops_per_token(cfg) + logits_flops(cfg)
            + attended * attn_flops_per_position(cfg))


def decode_flops(cfg: dict, tokens: int, attended: int) -> int:
    """Useful FLOPs of ``tokens`` decode steps that attend ``attended``
    positions in all."""
    return (tokens * (matmul_flops_per_token(cfg) + logits_flops(cfg))
            + attended * attn_flops_per_position(cfg))


def decode_least_bytes(cfg: dict, steps: int, attended: int) -> int:
    """The least HBM traffic of ``steps`` batched decode steps whose slots
    attend ``attended`` positions in all: the weights once per step and the
    live K/V at the true context lengths (never the gathered max_len
    view)."""
    return steps * weight_bytes(cfg) + attended * kv_bytes_per_token(cfg)
