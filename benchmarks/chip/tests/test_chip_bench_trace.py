"""Trace reduction on a small trace recorded on a TPU v5e.

``data/small.xplane.pb`` is what ``record_trace.py`` wrote on the chip:
three executions of a jitted ``decode`` (four bf16 1024x1024 matmul+tanh
fusions each) inside the harness's window span, with host sleeps between
them and a ``host_work`` span over the second gap.
"""
import pathlib

import pytest

from benchmarks.chip import xplane

SMALL = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(str(SMALL))


def test_window_is_the_harness_span(red):
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(0.015869339, abs=1e-9)


def test_busy_union_and_idle_share(red):
    assert red.busy_s == pytest.approx(142.082e-6, rel=1e-6)
    assert 0.99 < red.idle_share < 1.0


def test_programs_by_module_name(red):
    secs, count = red.modules["jit_decode"]
    assert count == 3
    assert secs == pytest.approx(3 * 47.373e-6, rel=1e-3)


def test_top_ops_are_self_times_with_short_names(red):
    names = [n for n, _ in red.top_ops]
    assert names[0].startswith("%convolution_tanh_fusion")
    assert all("{" not in n and len(n) < 130 for n in names)
    assert sum(s for _, s in red.top_ops) <= red.busy_s * (1 + 1e-6)


def test_idle_gaps_are_labelled_by_host_spans(red):
    labels = [label for label, _ in red.idle_gaps]
    secs = [s for _, s in red.idle_gaps]
    assert secs == sorted(secs, reverse=True)
    assert labels[0] == "python3: host_work"
    assert secs[0] == pytest.approx(0.0055, abs=2e-4)
    assert "python3: $time sleep" in labels[1:4]


def test_a_trace_without_a_tpu_plane_is_refused(tmp_path):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    jax.profiler.start_trace(str(tmp_path))
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(ValueError, match="no TPU device plane"):
        xplane.reduce(xplane.find_xplane(str(tmp_path)))
