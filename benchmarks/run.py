"""Benchmark harness: one module per paper table/figure (+ serve/kernel perf).

    PYTHONPATH=src python -m benchmarks.run [--only table1,fig14,serve,...]

Prints a ``name,us_per_call,derived`` CSV row per measurement (plus each
module's human-readable table in verbose mode).  The ``serve`` and
``kernels`` modules additionally persist their rows to ``BENCH_serve.json``
and ``BENCH_kernels.json`` at the repo root — the perf baseline future PRs
compare against.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

from . import (fig10_dse, fig11_perf, fig12_13_energy, fig14_correlation,
               fig15_noise, fig16_saf, kernels_bench, roofline_report,
               serve_bench, table1_acam_rows, table3_naf)

MODULES = {
    "table1": table1_acam_rows,
    "fig10": fig10_dse,
    "fig11": fig11_perf,
    "fig12_13": fig12_13_energy,
    "fig14": fig14_correlation,
    "fig15": fig15_noise,
    "fig16": fig16_saf,
    "table3": table3_naf,
    "kernels": kernels_bench,
    "serve": serve_bench,
    "roofline": roofline_report,
}

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_OUT = {"serve": "BENCH_serve.json", "kernels": "BENCH_kernels.json"}


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated module keys (default: all)")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)
    keys = args.only.split(",") if args.only else list(MODULES)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    all_rows = []
    failures = 0
    for key in keys:
        mod = MODULES[key]
        print(f"\n=== {key} ({mod.__name__}) ===")
        t0 = time.time()
        try:
            rows = mod.main(verbose=not args.quiet)
            all_rows.extend(rows or [])
            if key in JSON_OUT and rows:
                path = os.path.join(_REPO_ROOT, JSON_OUT[key])
                with open(path, "w") as f:
                    json.dump({"module": key, "rows": rows}, f, indent=1)
                print(f"--- wrote {JSON_OUT[key]}")
            print(f"--- {key} done in {time.time() - t0:.1f}s")
        except Exception as e:
            failures += 1
            print(f"--- {key} FAILED: {type(e).__name__}: {e}")
            traceback.print_exc()

    print("\n=== CSV (name,us_per_call,derived) ===")
    for r in all_rows:
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")
    print(f"\n{len(all_rows)} rows, {failures} module failures")
    return failures


if __name__ == "__main__":
    sys.exit(main())
