"""90th percentile over the requests due in the window of (finish - first
token) / (tokens - 1).  A request that did not finish counts with the
time the run stopped waiting for it as its finish."""
from benchmarks.chip.harness import p90


def read(run):
    vals = []
    for rid in run.due_in_window():
        n = run.requests[rid].max_new_tokens
        if n < 2:
            continue
        rec = run.records.get(rid)
        first = rec.first_token_s if rec is not None else None
        first = run.t_end if first is None else first
        fin = rec.finish_s if rec is not None else None
        fin = run.t_end if fin is None else fin
        vals.append((fin - first) / (n - 1))
    return 1e3 * p90(vals) if vals else None
