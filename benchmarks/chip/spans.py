"""The program's telemetry spans as the metric readers see them.

``run.events`` is the program's event trace (``repro.obs.telemetry``,
schema v3): lifecycle events, and spans that carry their start ``t0``
beside their end ``t``, an id ``span`` and the ``parent`` id.  Every
helper returns None where the trace cannot answer: the ring dropped
events (its first ``seq`` is not 0), or the program has no such spans.
"""
from __future__ import annotations


def events(run, kind: str) -> list[dict] | None:
    """The events of one kind, oldest first; None if the ring dropped any
    event of the run."""
    if not run.events or run.events[0]["seq"] != 0:
        return None
    return [e for e in run.events if e["ev"] == kind]


def waves(run) -> list[dict] | None:
    """The admission waves as spans (``t0``, ``rids``), or None."""
    ws = events(run, "admission_wave")
    if not ws or "t0" not in ws[0]:
        return None
    return ws


def ttft_parts(run) -> dict | None:
    """rid -> (generator lateness, ingest lag, scheduler wait, wave) in
    seconds, for each request due in the window that was submitted:

    * generator lateness: the harness's submit stamp - due time;
    * ingest lag: the scheduler's ``enqueue`` - the caller's ``submit``;
    * scheduler wait: start of the ``admission_wave`` whose ``rids`` hold
      the request - its ``enqueue``;
    * wave: its first token (``RequestRecord.first_token_s``) - that
      wave's start.

    A stamp the request never reached counts as the time the run stopped
    waiting (``run.t_end``).  The four add up to due -> first token, less
    the microseconds between the program's submit event and the harness's
    stamp.  None if the program emits no submit events or no wave spans.
    """
    subs, ws = events(run, "submit"), waves(run)
    if not subs or ws is None:
        return None
    submit = {e["rid"]: e["t"] for e in subs}
    enqueue = {e["rid"]: e["t"] for e in events(run, "enqueue")}
    start = {rid: w["t0"] for w in ws for rid in w["rids"]}
    parts = {}
    for rid in run.due_in_window():
        if rid not in submit or rid not in run.submitted:
            continue
        t_enq = enqueue.get(rid, run.t_end)
        t_wave = start.get(rid, run.t_end)
        rec = run.records.get(rid)
        t_first = (rec.first_token_s if rec is not None
                   and rec.first_token_s is not None else run.t_end)
        parts[rid] = (run.submitted[rid] - run.due[rid],
                      t_enq - submit[rid], t_wave - t_enq, t_first - t_wave)
    return parts
