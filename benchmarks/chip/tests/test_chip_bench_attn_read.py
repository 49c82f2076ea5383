"""``attn_read_share``: the pages paged attention reads over the pages a
dense view gathers, summed over the decode ticks harvested in the window,
from the ``decode_block`` spans' attributes."""
import pathlib

import pytest

from benchmarks.chip import harness

HERE = pathlib.Path(__file__).resolve().parents[1]


def _tick(span, read, view, seq):
    return {"ev": "decode_block", "t": 0.0, "tick": 0, "seq": seq,
            "t0": 0.0, "span": span, "parent": None, "n_active": 2,
            "block": 4, "kv_blocks_read": read, "kv_blocks_view": view}


def _harvest(parent, t, seq):
    return {"ev": "harvest", "t": t, "tick": 0, "seq": seq, "t0": t,
            "span": 100 + seq, "parent": parent, "rids": [],
            "n_emitted": []}


class _Run:
    window = (1.0, 2.0)

    def __init__(self, events):
        self.events = events

    def in_window(self, t):
        return self.window[0] <= t <= self.window[1]


@pytest.mark.parametrize("name", ["attn_read_share.chat",
                                  "attn_read_share.batch"])
def test_reads_the_ticks_harvested_in_the_window(name):
    run = _Run([_tick(0, 10, 64, 0), _harvest(0, 0.5, 1),   # before
                _tick(2, 12, 64, 2), _harvest(2, 1.5, 3),
                _tick(4, 20, 64, 4), _harvest(4, 1.9, 5),
                _tick(6, 30, 64, 6), _harvest(6, 2.5, 7)])  # after
    got = harness.read_metric(name, run, (HERE,))
    assert got == pytest.approx(100.0 * (12 + 20) / (64 + 64))


def test_reads_nothing_without_page_counts():
    """The dense view leaves the attributes None; a program from before
    them has no such fields; a cut ring reads nothing either."""
    name = "attn_read_share.batch"
    run = _Run([_tick(0, None, None, 0), _harvest(0, 1.5, 1)])
    assert harness.read_metric(name, run, (HERE,)) is None
    old = _tick(0, 1, 2, 0)
    del old["kv_blocks_read"], old["kv_blocks_view"]
    run = _Run([old, _harvest(0, 1.5, 1)])
    assert harness.read_metric(name, run, (HERE,)) is None
    run = _Run([dict(_tick(0, 8, 64, 5)), _harvest(0, 1.5, 6)])
    assert harness.read_metric(name, run, (HERE,)) is None
