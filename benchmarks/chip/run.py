"""Run one cell of the benchmark on the chip this process holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness check compared, beside its limit).  The same numbers are the
last lines of standard error.  With no TPU, or fewer chips than the cell
asks for, it prints no result and exits 2.

``--rehearse`` runs the same machinery on any backend at the
configuration's small rehearsal size; without a TPU it then still prints
no result and exits 2, and it never prints one for the small size.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="rehearse at the small size on any backend; "
                        "prints no result")
    args = p.parse_args(argv)
    # import the benchmark as a package and the program from its source tree
    sys.path[:0] = [str(REPO), str(REPO / "src")]

    from benchmarks.chip import harness
    try:
        result = harness.execute(args.workload, args.seed, args.seconds,
                                 bool(args.trace), t_process=T_PROCESS,
                                 rehearse=args.rehearse)
    except harness.NoChip as e:
        print(f"run: {e}; no result", file=sys.stderr)
        return 2
    result.pop("_detail", None)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    if args.rehearse:
        platform = result["device"]["platform"]
        why = "no TPU" if platform != "tpu" else "rehearsal size"
        print(f"run: rehearsal on {platform} finished; {why}: no result",
              file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
