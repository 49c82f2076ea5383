"""Async front end ingest lag: 90th percentile over the requests due in
the window of the scheduler thread's ingest stamp (the program's
``enqueue`` event) minus the caller's ``submit`` event, host clock.  A
request never ingested counts to the time the run stopped waiting."""
from benchmarks.chip import spans
from benchmarks.chip.harness import p90


def read(run):
    parts = spans.ttft_parts(run)
    if not parts:
        return None
    return 1e3 * p90([p[1] for p in parts.values()])
