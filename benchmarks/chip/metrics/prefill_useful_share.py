"""Prefill rows that did useful work, in percent: prompt tokens computed
(prompt minus prefix reuse) over the rows the chunk programs computed
(admission waves x chunks x slots x chunk length), for waves that ended
in the window."""


def read(run):
    rows = sum(e["n_chunks"] for e in run.events
               if e["ev"] == "admission_wave" and run.in_window(e["t"]))
    rows *= run.slots * run.prefill_chunk
    useful = sum(r.prompt_len - r.reuse for r in run.admitted_in_window())
    return 100.0 * useful / rows if rows else None
