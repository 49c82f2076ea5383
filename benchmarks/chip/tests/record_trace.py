"""Record the small traces that tests/test_chip_bench_trace.py reduces.

    python3 benchmarks/chip/tests/record_trace.py <out-dir> [--chips 4]

On a TPU: three executions of a jitted ``decode`` with host sleeps
between them, inside the harness's window annotation, with a host span
named ``host_work`` over the second gap.  Writes the ``.xplane.pb``
under ``<out-dir>`` and prints the planes and lines it holds.

With ``--chips 4`` the ``decode`` runs tensor parallel on a mesh of four
chips, as the program's serve rules place a layer: an activation split
by columns is gathered whole before a replicated weight (an all-gather),
and a matmul split along its contraction is summed over the chips (an
all-reduce).  It then also prints the trace's reduction.
"""
from __future__ import annotations

import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def _mesh_decode(chips: int):
    """A jitted tensor-parallel ``decode`` over ``chips`` chips and its
    argument, placed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:chips]), ("model",))

    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))

    def whole(a):
        return jax.lax.with_sharding_constraint(a, NamedSharding(mesh, P()))

    def decode(x, w_col, w_row):
        for _ in range(2):
            h = jnp.tanh(whole(x) @ w_col)      # all-gather, split columns
            x = jnp.tanh(whole(h @ w_row))      # split contraction: sum
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, "model")))
        return x

    n = 1024
    x = put(jnp.ones((256, n), jnp.bfloat16) / n, P(None, "model"))
    w_col = put(jnp.ones((n, n), jnp.bfloat16) / n, P(None, "model"))
    w_row = put(jnp.ones((n, n), jnp.bfloat16) / n, P("model", None))
    return jax.jit(decode), (x, w_col, w_row)


def main(out: str, chips: int = 1) -> int:
    sys.path.insert(0, str(HERE.parents[2]))
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import xplane
    from benchmarks.chip.xplane import WINDOW_SPAN, find_xplane

    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < chips:
        print(f"record_trace: no TPU with {chips} chips", file=sys.stderr)
        return 2

    if chips == 1:
        def decode(x):
            for _ in range(4):
                x = jnp.tanh(x @ x)
            return x

        f = jax.jit(decode)
        args = (jnp.ones((1024, 1024), jnp.bfloat16) / 1024,)
    else:
        f, args = _mesh_decode(chips)
        print(f.lower(*args).compile().as_text()[-4000:])
    f(*args).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation(WINDOW_SPAN):
        time.sleep(0.002)
        f(*args).block_until_ready()
        time.sleep(0.003)
        f(*args).block_until_ready()
        with jax.profiler.TraceAnnotation("host_work"):
            time.sleep(0.004)
        f(*args).block_until_ready()
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
    if chips > 1:
        red = xplane.reduce(path)
        print("reduced", red)
        for plane in pd.planes:
            for line in plane.lines:
                names = {e.name for e in line.events}
                print("collectives", plane.name, line.name,
                      sorted(n for n in names if xplane.is_collective(n)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[3]) if len(sys.argv) > 3
                  and sys.argv[2] == "--chips" else 1))
