"""Record the small trace that tests/test_chip_bench_trace.py reduces.

    python3 benchmarks/chip/tests/record_trace.py <out-dir>

On a TPU: three executions of a jitted ``decode`` with host sleeps
between them, inside the harness's window annotation, with a host span
named ``host_work`` over the second gap.  Writes the ``.xplane.pb``
under ``<out-dir>`` and prints the planes and lines it holds.
"""
from __future__ import annotations

import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def main(out: str) -> int:
    sys.path.insert(0, str(HERE.parents[2]))
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.xplane import WINDOW_SPAN, find_xplane

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2

    def decode(x):
        for _ in range(4):
            x = jnp.tanh(x @ x)
        return x

    f = jax.jit(decode)
    x = jnp.ones((1024, 1024), jnp.bfloat16) / 1024
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        time.sleep(0.002)
        f(x).block_until_ready()
        time.sleep(0.003)
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host_work"):
            time.sleep(0.004)
        f(x).block_until_ready()
        time.sleep(0.002)
    jax.profiler.stop_trace()
    path = find_xplane(out)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", line.name, len(evs),
                  [(e.name, e.start_ns, e.duration_ns) for e in evs[:4]])
    print("xplane", path, pathlib.Path(path).stat().st_size)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
