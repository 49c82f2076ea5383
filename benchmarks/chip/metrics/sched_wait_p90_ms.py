"""Scheduler wait: 90th percentile over the requests due in the window of
the start (``t0``) of the ``admission_wave`` span that admitted the
request minus its ingest (``enqueue``), host clock: the wait for a wave,
pipeline flush included.  A request no wave took counts to the time the
run stopped waiting."""
from benchmarks.chip import spans
from benchmarks.chip.harness import p90


def read(run):
    parts = spans.ttft_parts(run)
    if not parts:
        return None
    return 1e3 * p90([p[2] for p in parts.values()])
