"""Exposed collective time over busy time in the traced stretch, in
percent: the seconds in which a collective operation runs on a chip and
no other operation does, over the seconds in which any operation runs,
each averaged over the chips (``xplane.collective_times``).  A trace with
no collective reads nothing."""


def read(run):
    red = run.reduced
    if red is None or red.collective_s <= 0 or red.busy_s <= 0:
        return None
    return 100.0 * red.collective_exposed_s / red.busy_s
