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


def _plane(name, lines):
    from types import SimpleNamespace as NS
    return NS(name=name, lines=[
        NS(name=line, events=[NS(name=n, start_ns=s, end_ns=e,
                                 duration_ns=e - s) for n, s, e in evs])
        for line, evs in lines.items()])


def test_collectives_split_into_exposed_and_hidden():
    """A synthetic two-chip trace.  Chip 0, inside a loop over [0, 100]
    ns: compute, a synchronous all-gather [30, 40], compute, an
    all-reduce started at 60 and done at 90-95 with compute between.  Its
    asynchronous span [60, 95] is collective time; only the all-gather
    and the two halves run with nothing else: 10 + 2 + 5 ns exposed.
    Chip 1 runs no collective."""
    ag = ("%all-gather.3 = bf16[32,1,28,128]{3,0,1,2} all-gather(%x), "
          "dimensions={2}")
    start = "%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %y)"
    done = ("%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} "
            "%all-reduce-start.1)")
    loop = "%while.2 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t)"
    reads = "%fusion.9 = bf16[8]{0} fusion(%all-gather.3), kind=kLoop"
    ops0 = [(loop, 0, 100), ("%fusion.1 = bf16[8] fusion(%a)", 0, 30),
            (ag, 30, 40), (reads, 40, 60), (start, 60, 62),
            ("%fusion.2 = bf16[8] fusion(%b)", 62, 90), (done, 90, 95)]
    ops1 = [("%fusion.1 = bf16[8] fusion(%a)", 0, 100)]
    red = xplane.reduce_planes([
        _plane("/device:TPU:0", {"XLA Ops": ops0, "XLA Modules": [
            ("jit_decode(1)", 0, 100)], "Async XLA Ops": [(start, 60, 95)]}),
        _plane("/device:TPU:1", {"XLA Ops": ops1, "XLA Modules": [
            ("jit_decode(1)", 0, 100)]})])
    assert [xplane.is_collective(n) for n in (ag, start, done, loop, reads)] \
        == [True, True, True, False, False]
    assert red.n_devices == 2
    assert red.busy_s == pytest.approx(100e-9)
    assert red.collective_s == pytest.approx((10 + 35) * 1e-9 / 2)
    assert red.collective_exposed_s == pytest.approx((10 + 2 + 5) * 1e-9 / 2)


def test_a_trace_without_collectives_reads_none(red):
    from benchmarks.chip.metrics import collective_exposed_share
    assert red.collective_s == 0 and red.collective_exposed_s == 0

    class Run:
        reduced = red

    assert collective_exposed_share.read(Run()) is None
