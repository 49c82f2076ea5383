"""Run one cell several times, one process per run, and summarise.

    python3 benchmarks/chip/sets.py --workload <cell> --seeds 11,12,13 \
        --seconds 30 [--trace 0|1] [--out runs/<file>.jsonl]

Each run is ``run.py`` in a process of its own, one after the other, so
each holds the chip alone.  Every run's seed, exit code, wall time,
result line and the end of its standard error go to ``--out`` as one
JSON line; the summary gives, per metric, the median and the spread
(the distance between the first and third quartiles of
``statistics.quantiles(values, n=4)``, as a share of the median) of the
runs after the first (which compiles), and each run's checks.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def spread(values) -> float | None:
    if len(values) < 2:
        return None
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else None


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    result = None
    if p.returncode == 0 and lines:
        try:
            result = json.loads(lines[-1])
        except json.JSONDecodeError:
            result = None
    return {"workload": workload, "seed": seed, "seconds": seconds,
            "trace": trace, "rc": p.returncode, "wall_s": wall,
            "result": result, "stderr_tail": p.stderr[-4000:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = pathlib.Path(args.out) if args.out else None
    if out:
        out.parent.mkdir(parents=True, exist_ok=True)
    runs = []
    for seed in seeds:
        r = one(args.workload, seed, args.seconds, args.trace)
        runs.append(r)
        if out:
            with out.open("a") as f:
                f.write(json.dumps(r) + "\n")
        res = r["result"] or {}
        m = {k: round(v["value"], 4) for k, v in
             res.get("metrics", {}).items()}
        c = {k: v["value"] for k, v in res.get("checks", {}).items()}
        print(f"seed {seed} rc {r['rc']} wall {r['wall_s']:.1f}s "
              f"correct {res.get('correct')} attempted "
              f"{res.get('attempted')} failed {res.get('failed')} "
              f"metrics {m} checks {c}", flush=True)
        if r["rc"] != 0:
            print(r["stderr_tail"][-2000:], flush=True)
    names = sorted({k for r in runs[1:] if r["result"]
                    for k in r["result"]["metrics"]})
    for k in names:
        vals = [r["result"]["metrics"][k]["value"] for r in runs[1:]
                if r["result"] and k in r["result"]["metrics"]]
        if vals:
            print(f"summary {k}: median {statistics.median(vals):.6g} "
                  f"spread {spread(vals)} n {len(vals)}", flush=True)
    return 0 if all(r["rc"] == 0 for r in runs) else 1


if __name__ == "__main__":
    sys.exit(main())
