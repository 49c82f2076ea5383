"""How late the load generator submitted: 90th percentile of submit time
minus due time over the requests due in the window (host clock)."""
from benchmarks.chip.harness import p90


def read(run):
    vals = [run.submitted[rid] - run.due[rid] for rid in run.due_in_window()
            if rid in run.submitted]
    return 1e3 * p90(vals) if vals else None
