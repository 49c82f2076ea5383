"""Host time of one decode tick on the scheduler thread: the summed
durations of the ``dispatch`` and ``harvest`` spans of the ticks
harvested in the window divided by the number of those ticks (host
clock).  The drain thread's wait for the device is not in it."""
from benchmarks.chip import spans


def read(run):
    harvests, dispatches = spans.events(run, "harvest"), \
        spans.events(run, "dispatch")
    if not harvests or not dispatches:
        return None
    dispatch = {e["parent"]: e["t"] - e["t0"] for e in dispatches
                if e["parent"] is not None}
    total, ticks = 0.0, 0
    for h in harvests:
        if run.in_window(h["t"]) and h["parent"] in dispatch:
            total += dispatch[h["parent"]] + h["t"] - h["t0"]
            ticks += 1
    return 1e3 * total / ticks if ticks else None
