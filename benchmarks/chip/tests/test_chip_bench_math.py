"""Peaks, bytes and FLOPs kept with the benchmark, against hand numbers."""
import json
import pathlib

import pytest

from benchmarks.chip import model_math

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,weights_gb,kv_per_token", [
    ("qwen2_5_3b", 6.17, 36864),
    ("minicpm_2b", 5.45, 368640),
])
def test_weight_and_kv_bytes(name, weights_gb, kv_per_token):
    c = cfg(name)
    assert model_math.weight_bytes(c) / 1e9 == pytest.approx(weights_gb,
                                                              abs=0.005)
    assert model_math.kv_bytes_per_token(c) == kv_per_token


def test_param_counts():
    assert model_math.param_count(cfg("qwen2_5_3b")) == 3_085_938_688
    assert model_math.param_count(cfg("minicpm_2b")) / 1e9 == \
        pytest.approx(2.725, abs=0.001)


def test_peaks_known_and_unknown():
    p = model_math.peaks("TPU v5 lite")
    assert p["bf16_flop_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        model_math.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        model_math.peaks("cpu")


def test_flops_agree_between_prefill_and_decode():
    c = cfg("qwen2_5_3b")
    # decoding position p attends p + 1 positions; a one-token prefill at
    # position p is the same work
    p = 1000
    one = model_math.prefill_flops(c, p + 1, p)
    assert one == model_math.decode_flops(c, 1, p + 1)
    # ~6.2 GFLOP per generated token at short context
    assert model_math.decode_flops(c, 1, 128) / 1e9 == pytest.approx(
        6.17, abs=0.1)


def test_decode_least_bytes_counts_weights_once_per_step():
    c = cfg("qwen2_5_3b")
    w = model_math.weight_bytes(c)
    assert model_math.decode_least_bytes(c, 4, 0) == 4 * w
    assert model_math.decode_least_bytes(c, 1, 1000) == w + 1000 * 36864
