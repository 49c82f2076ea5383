"""Share of the dense view's pages that paged attention reads: the
summed ``kv_blocks_read`` of the ``decode_block`` spans of the ticks
harvested in the window over their summed ``kv_blocks_view`` (program
span attributes, in %).  A program without the attributes — the dense
view, or a program from before the kernel path — reads nothing."""
from benchmarks.chip import spans


def read(run):
    harvests, ticks = spans.events(run, "harvest"), \
        spans.events(run, "decode_block")
    if not harvests or not ticks:
        return None
    blocks = {e["span"]: e for e in ticks
              if e.get("kv_blocks_read") is not None}
    read_, view = 0, 0
    for h in harvests:
        tick = blocks.get(h["parent"])
        if tick is not None and run.in_window(h["t"]):
            read_ += tick["kv_blocks_read"]
            view += tick["kv_blocks_view"]
    return 100.0 * read_ / view if view else None
