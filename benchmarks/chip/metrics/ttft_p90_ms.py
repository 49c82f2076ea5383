"""90th percentile over the requests due in the window of due time to
first token.  A request that never got its first token counts as late by
the time the run stopped waiting for it."""
from benchmarks.chip.harness import p90


def read(run):
    rids = run.due_in_window()
    if not rids:
        return None
    vals = []
    for rid in rids:
        rec = run.records.get(rid)
        t = rec.first_token_s if rec is not None else None
        vals.append((run.t_end if t is None else t) - run.due[rid])
    return 1e3 * p90(vals)
