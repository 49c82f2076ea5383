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
    ("qwen2_7b", 15.23, 57344),
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
    # 28 layers of 233,057,792, an embedding and an untied readout of
    # 152,064 x 3,584 each, the final norm
    c = cfg("qwen2_7b")
    assert model_math.layer_params(c) == 233_057_792
    assert model_math.param_count(c) == 7_615_616_512


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


def _reader(name):
    import importlib.util
    path = CONFIGS.parent / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"m_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("name", ["decode_roofline", "mfu.chat",
                                  "mfu.batch"])
def test_peak_readers_count_the_chips(name):
    """The same run read as four chips gives a quarter of what it gives
    as one: the peaks are the chips' together."""
    from benchmarks.chip.harness import Run

    class Reduced:
        modules = {"jit_decode": (0.8, 10)}      # 20 ms a step

    class Rec:
        prompt_len, reuse, admit_s = 1400, 1024, 1.0

    def run(chips):
        return Run(cfg=cfg("qwen2_7b"), reduced=Reduced(), decode_block=4,
                   peaks=model_math.peaks("TPU v5 lite"), chips=chips,
                   window=(0.0, 10.0), profile=(0.0, 10.0),
                   harvests=[(t, 32, 32 * 900, 4) for t in range(1, 10)],
                   records={0: Rec()})

    read = _reader(name).read
    one, four = read(run(1)), read(run(4))
    assert one > 0
    assert four == pytest.approx(one / 4, rel=1e-12)
