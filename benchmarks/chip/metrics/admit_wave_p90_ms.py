"""Admission wave to first token: 90th percentile over the requests due
in the window that a wave admitted of their first-token stamp
(``RequestRecord.first_token_s``) minus the start (``t0``) of that
``admission_wave`` span, host clock: the wave's host plan, chunk
prefill and first-token sampling up to this request's token."""
from benchmarks.chip import spans
from benchmarks.chip.harness import p90


def read(run):
    parts, ws = spans.ttft_parts(run), spans.waves(run)
    if not parts:
        return None
    taken = {rid for w in ws for rid in w["rids"]}
    vals = [p[3] for rid, p in parts.items() if rid in taken]
    return 1e3 * p90(vals) if vals else None
