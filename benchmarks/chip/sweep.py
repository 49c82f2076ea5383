"""Find an open-loop cell's knee: the highest rate whose backlog does
not grow over the window.

    python3 benchmarks/chip/sweep.py --workload <cell> --rates 0.2,0.3 \
        --seeds 11,12 --seconds 51 [--out runs/<file>.jsonl]

One process on the chip serves the cell at each rate once per seed (set-up
is paid per run, from the compile cache after the first), without the
correctness check; a run waits past its close only until every request
due in its window is admitted.  Per run it prints the requests due in the
window, the number waiting for admission (due, not yet admitted) at the
window's open and close, the least-squares slope of that count over the
window (sampled every second, in requests per minute), the slope of queue
wait against due time (seconds of wait per second), and the p90 of queue
wait and of time to first token.  A rate grows its backlog when on every
seed the waiting count stays above 0 through the window's second half (a
burst that the queue drains again is no growth), and, averaged over the
seeds, the count rises by more than ``GROW_PER_MIN`` requests a minute
and queue wait rises with due time.  The sweep stops at the first such
rate; the knee is the highest rate below it, and its last line is
``knee <rate> cell_rate <0.8 x knee>``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
GROW_PER_MIN = 1.0


def _paths() -> None:
    """Import the benchmark as a package and the program from its source
    tree."""
    sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]


def waiting(run, t: float) -> int:
    """Requests due by ``t`` that were not yet admitted at ``t``."""
    n = 0
    for rid, due in run.due.items():
        if due > t:
            continue
        rec = run.records.get(rid)
        if rec is None or rec.admit_s is None or rec.admit_s > t:
            n += 1
    return n


def slope(x, y) -> float:
    return float(np.polyfit(x, y, 1)[0]) if len(set(x)) >= 2 else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _paths()

    from benchmarks.chip import harness
    from benchmarks.chip.metrics import queue_wait_p90_ms, ttft_p90_ms
    seeds = [int(s) for s in args.seeds.split(",")]
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        rows = []
        for seed in seeds:
            res = harness.execute(args.workload, seed, args.seconds, False,
                                  t_process=time.perf_counter(), check=False,
                                  arrival={"rate_req_s": rate},
                                  wait_for="admit")
            run = res["_detail"]["run"]
            lo, hi = run.window
            ts = np.arange(lo, hi + 1e-9, 1.0)
            counts = [waiting(run, t) for t in ts]
            due = run.due_in_window()
            waits = [((run.records[rid].admit_s if rid in run.records
                       and run.records[rid].admit_s is not None
                       else run.t_end) - run.due[rid]) for rid in due]
            row = {
                "rate_req_s": rate, "seconds": args.seconds, "seed": seed,
                "due_in_window": len(due),
                "waiting_open": waiting(run, lo),
                "waiting_close": waiting(run, hi),
                "waiting_min_second_half": min(
                    c for t, c in zip(ts, counts) if t >= (lo + hi) / 2),
                "waiting_slope_per_min": 60 * slope(ts - lo, counts),
                "queue_wait_slope": slope(
                    [run.due[rid] - lo for rid in due], waits),
                "queue_wait_p90_ms": queue_wait_p90_ms.read(run),
                "ttft_p90_ms": ttft_p90_ms.read(run),
                "setup_s": run.setup_s, "device": res["device"]}
            rows.append(row)
            print(json.dumps(row), flush=True)
            if args.out:
                pathlib.Path(args.out).parent.mkdir(parents=True,
                                                    exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
        grow = np.mean([r["waiting_slope_per_min"] for r in rows])
        trend = np.mean([r["queue_wait_slope"] for r in rows])
        drained = any(r["waiting_min_second_half"] == 0 for r in rows)
        if not drained and grow > GROW_PER_MIN and trend > 0:
            print(f"backlog grows at {rate} req/s ({grow:.2f} waiting per "
                  f"minute, queue wait {trend:+.3f} s/s); higher rates not "
                  "run", flush=True)
            break
        knee = rate
    if knee is None:
        print("no rate held", flush=True)
        return 1
    print(f"knee {knee} cell_rate {round(0.8 * knee, 4)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
