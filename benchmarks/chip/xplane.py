"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

Read with ``jax.profiler.ProfileData`` and nothing else.  The device
planes are those named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line
holds one event per executed operation and the ``XLA Modules`` line one
per executed program.  The window is the harness's ``bench_profile``
annotation on a host thread where the trace has it, else the span of the
device events.

* busy: the union of the operation intervals inside the window, averaged
  over the device planes; idle share = 1 - busy / window;
* per program: device seconds and executions, keyed by the module name
  without its ``(id)`` suffix (``jit_decode``);
* top operations by device self seconds (an operation's time less that
  of the operations nested in it, so a loop does not hide its body),
  named by the HLO instruction and its result shape;
* idle gaps: the stretches between busy intervals, each labelled with the
  host event that overlaps it most (the host thread's name and the
  event's), longest first.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench_profile"
_DEVICE = re.compile(r"^/device:TPU:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_label(text: str) -> str:
    """``%fusion.2 = bf16[8,128]{1,0:T(8,128)} fusion(...)`` ->
    ``%fusion.2 = bf16[8,128] fusion``."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:120]
    rest = _LAYOUT.sub("", rest)
    shape, _, tail = rest.partition(" ")
    kind = tail.split("(", 1)[0]
    return f"{name} = {shape[:80]} {kind}".strip()


def _self_times(events):
    """(name, self seconds) of properly nested events on one line."""
    out = []
    stack = []                       # [end, name, duration, children]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][0]:
            end, n, dur, kids = stack.pop()
            out.append((n, max(dur - kids, 0)))
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0])
    while stack:
        end, n, dur, kids = stack.pop()
        out.append((n, max(dur - kids, 0)))
    return out


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    modules: dict[str, tuple[float, int]]      # name -> (seconds, count)
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(path: str, *, top: int = 10) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, hosts = [], []
    window = None
    for plane in pd.planes:
        if _DEVICE.match(plane.name):
            devices.append(plane)
            continue
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns) for e in line.events
                   if e.duration_ns > 0]
            for name, s, e in evs:
                if name == WINDOW_SPAN:
                    window = (s, e)
            hosts.append((line.name, evs))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")

    per_dev = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        ops_line = lines.get("XLA Ops") or lines.get("XLA Modules")
        ops = ([(e.name, e.start_ns, e.end_ns) for e in ops_line.events]
               if ops_line is not None else [])
        mods = ([(e.name, e.start_ns, e.end_ns)
                 for e in lines["XLA Modules"].events]
                if "XLA Modules" in lines else [])
        per_dev.append((ops, mods))
    if window is None:
        starts = [s for ops, _ in per_dev for _, s, _ in ops]
        ends = [e for ops, _ in per_dev for _, _, e in ops]
        if not starts:
            raise ValueError(f"{path}: no device operation in the trace")
        window = (min(starts), max(ends))
    lo, hi = window
    window_s = (hi - lo) * 1e-9

    busy_total = 0.0
    modules: dict[str, list] = {}
    op_time: dict[str, float] = {}
    gaps = []
    for i, (ops, mods) in enumerate(per_dev):
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        inside = [(n, s, e) for n, s, e in ops if s >= lo and e <= hi]
        for name, secs in _self_times(inside):
            key = op_label(name)
            op_time[key] = op_time.get(key, 0.0) + secs * 1e-9
        for name, s, e in mods:
            if s >= lo and e <= hi:
                m = modules.setdefault(_SUFFIX.sub("", name), [0.0, 0])
                m[0] += (e - s) * 1e-9
                m[1] += 1
        if i == 0:                       # gaps are read on the first chip
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            gaps = [(edges[j], edges[j + 1])
                    for j in range(0, len(edges), 2)
                    if edges[j + 1] > edges[j]]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0, "no host event"
        for line_name, evs in hosts:
            for name, hs, he in evs:
                if name == WINDOW_SPAN:
                    continue
                ov = min(e, he) - max(s, hs)
                if ov > best:
                    best = ov
                    label = f"{line_name}: {name}" if line_name else name
        labelled.append((label, (e - s) * 1e-9))
    n = len(devices)
    return Reduced(
        window_s=window_s, busy_s=busy_total / n, n_devices=n,
        modules={k: (v[0] / n, v[1] // n) for k, v in modules.items()},
        top_ops=sorted(((k, v / n) for k, v in op_time.items()),
                       key=lambda kv: -kv[1])[:top],
        idle_gaps=labelled)
