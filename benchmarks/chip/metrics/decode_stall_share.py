"""Share of decode time that admission waves stall, in percent: over the
requests due in the window, the sum of the overlaps of every
``admission_wave`` span with the request's decode interval [first token,
finish] divided by the sum of those intervals' lengths (host clock).  A
request unfinished when the run stopped waiting counts that time as its
finish.  Waves run on the scheduler thread, which dispatches no decode
tick while one runs."""
from benchmarks.chip import spans


def read(run):
    ws = spans.waves(run)
    if ws is None:
        return None
    stalled = decoding = 0.0
    for rid in run.due_in_window():
        rec = run.records.get(rid)
        if rec is None or rec.first_token_s is None:
            continue
        a = rec.first_token_s
        b = run.t_end if rec.finish_s is None else rec.finish_s
        decoding += b - a
        stalled += sum(max(0.0, min(b, w["t"]) - max(a, w["t0"]))
                       for w in ws)
    return 100.0 * stalled / decoding if decoding > 0 else None
