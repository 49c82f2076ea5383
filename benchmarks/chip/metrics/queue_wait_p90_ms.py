"""Scheduler queue wait: 90th percentile of due time to the program's
admit stamp (RequestRecord.admit_s, taken when the admission wave that
prefilled the request ends) over the requests due in the window."""
from benchmarks.chip.harness import p90


def read(run):
    rids = run.due_in_window()
    if not rids:
        return None
    vals = []
    for rid in rids:
        rec = run.records.get(rid)
        t = rec.admit_s if rec is not None else None
        vals.append((run.t_end if t is None else t) - run.due[rid])
    return 1e3 * p90(vals)
