"""Read the correctness check's two ends on the chip: the program's
widest logit gap and the float8 control's, on the same served requests.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out runs/<file>.jsonl]

For each seed one run of the cell at its own size and load (one process,
one run after another), then the plain reference reads the sampled
requests twice: in float32 (the program's reading) and with every
matrix product's inputs in float8 e4m3 (the control: at each position,
the float32 gap of the token that float8 puts first).  The harness's
check judges the control in the program's place (``control_correct``,
which has to come out false); ``correct`` is the program's.  A limit
lies between the program's largest reading over many seeds and the
control's smallest.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def _paths() -> None:
    """Import the benchmark as a package and the program from its source
    tree."""
    sys.path[:0] = [str(HERE.parents[1]), str(HERE.parents[1] / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    _paths()

    from benchmarks.chip import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.execute(args.workload, seed, args.seconds, False,
                              t_process=time.perf_counter(), control=True)
        d, chk = res["_detail"], res["checks"]
        program_correct = (
            d["program_max_gap"] <= chk["max_logit_gap"]["limit"]
            and chk["token_count_mismatches"]["value"] <= 0)
        row = {"workload": args.workload, "seed": seed,
               "program_max_gap": d["program_max_gap"],
               "control_max_gap": chk["max_logit_gap"]["value"],
               "limit": chk["max_logit_gap"]["limit"],
               "gaps": d["gaps"], "control_gaps": d["control_gaps"],
               "sample": d["sample"], "reference_s": d["reference_s"],
               "correct": program_correct,
               "control_correct": res["correct"], "device": res["device"]}
        print(json.dumps(row), flush=True)
        if args.out:
            pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
