"""Rehearse a four-chip cell on four virtual CPU devices, one process.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python3 benchmarks/chip/tests/mesh_rehearsal.py <cell> <seed>

The tests of ``test_chip_bench_mesh.py`` start it in a process of their
own, as the device count is fixed when JAX starts.  It prints one JSON
line: whether ``weights.make`` gives the same bits with the cell's
sharding tree as on one device, and, at the rehearsal size past the
harness's look for a chip, the check of an unbroken run, of the float8
control in the program's place, and of runs with the timed path broken
underneath (``faults.py``).  The unbroken run goes through ``run.py``'s
``main``, as the benchmark's command runs it, with what it prints and its
exit code.
"""
from __future__ import annotations

import contextlib
import io
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
SECONDS = 4.0
# an open-loop mix at its rehearsal size is a request or two in a short
# window: a higher rate gives the check some hundred tokens to compare
ARRIVAL = {"rate_req_s": 6.0}


def main(cell: str, seed: int) -> int:
    sys.path[:0] = [str(HERE.parents[2]), str(HERE.parents[2] / "src")]
    import jax
    import numpy as np

    from benchmarks.chip import harness, run, weights
    from benchmarks.chip.tests import faults
    from repro.configs import get_config
    from repro.parallel.sharding import serve_exact_rules

    c = harness.load_cell(cell)
    cfg = harness.rehearsal_cfg(c["cfg"])
    shape = harness.mesh_shape(c["cell"], cfg)
    mesh = harness._mesh(jax.devices()[:shape[0] * shape[1]], shape)
    placed = harness._param_shardings(get_config(cfg["arch"], reduced=True),
                                      mesh, serve_exact_rules())
    one = weights.make(cfg, seed, np.float32)
    many = weights.make(cfg, seed, np.float32, placed)
    out = {
        "devices": len(jax.devices()),
        "same_bits": all(np.array_equal(np.asarray(a), np.asarray(b))
                         for a, b in zip(jax.tree.leaves(one),
                                         jax.tree.leaves(many))),
        "sharded_leaves": sum(not x.sharding.is_fully_replicated
                              for x in jax.tree.leaves(many)),
        "runs": {}}

    def record(name, res):
        d = res["_detail"]
        out["runs"][name] = {
            "correct": res["correct"], "checks": res["checks"],
            "program_max_gap": d["program_max_gap"],
            "compared_tokens": d["compared_tokens"],
            "count": res["device"]["count"]}

    execute = harness.execute

    def unbroken(*args, **kw):
        res = execute(*args, arrival=ARRIVAL, **kw)
        record("unbroken", res)
        return res

    harness.execute = unbroken
    printed, logged = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(printed), \
            contextlib.redirect_stderr(logged):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(SECONDS), "--trace", "0", "--rehearse"])
    harness.execute = execute
    out["run_py"] = {"rc": rc, "stdout": printed.getvalue(),
                     "stderr": logged.getvalue()[-6000:]}
    for name, kw in [("control", {"control": True})] + [
            (f.__name__, {"tamper": f}) for f in faults.FAULTS]:
        record(name, execute(cell, seed, SECONDS, False,
                             t_process=time.perf_counter(), rehearse=True,
                             arrival=ARRIVAL, **kw))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], int(sys.argv[2])))
