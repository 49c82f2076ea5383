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
  event's), longest first;
* collectives (``all-gather``, ``all-reduce``, ``reduce-scatter``,
  ``collective-permute``, ``all-to-all`` and their ``-start``/``-done``
  halves, known by the HLO opcode or the instruction's name), per chip
  and averaged over the chips: their time, the union of their events on
  the ``XLA Ops`` line and of their whole asynchronous spans on the
  ``Async XLA Ops`` line; and the exposed part of it, the time of their
  ``XLA Ops`` events in which no other operation runs (an operation that
  encloses others, such as a loop, runs only where none of its nested
  operations does).  The rest of an asynchronous span is hidden under
  other work.
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
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_STEM = re.compile(r"^%?([a-z][a-z0-9-]*?)(\.\d+)*$")
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute|all-to-all)(-start|-done)?$")
_ANY_COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|"
                             r"collective-permute|all-to-all")


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


def is_collective(text: str) -> bool:
    """Whether an operation's HLO text (``%all-gather-start.3 = ...
    all-gather-start(...)``) names a collective, by opcode or by name."""
    if not _ANY_COLLECTIVE.search(text):
        return False
    name, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + _LAYOUT.sub("", rest))
    if m and _COLLECTIVE.match(m.group(1)):
        return True
    m = _STEM.match(name.strip())
    return bool(m and _COLLECTIVE.match(m.group(1)))


def _subtract(intervals, cuts):
    """Sorted disjoint ``intervals`` less the sorted disjoint ``cuts``."""
    out, j = [], 0
    for s, e in intervals:
        while j < len(cuts) and cuts[j][1] <= s:
            j += 1
        k = j
        while k < len(cuts) and cuts[k][0] < e:
            if cuts[k][0] > s:
                out.append((s, cuts[k][0]))
            s = max(s, cuts[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def _self_intervals(events):
    """(name, intervals in which it runs and none nested in it does) of
    properly nested events on one line."""
    out = []
    stack = []                       # [end, name, start, children]

    def close(item):
        end, n, start, kids = item
        out.append((n, _subtract([(start, end)], _union(kids))))

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][3].append((s, e))
        stack.append([e, name, s, []])
    while stack:
        close(stack.pop())
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def collective_times(selfs, async_ops, lo, hi) -> tuple[float, float]:
    """(collective, exposed) nanoseconds of one chip inside [lo, hi]:
    ``selfs`` its ``XLA Ops`` events inside it as ``_self_intervals``
    gives them, ``async_ops`` its ``Async XLA Ops`` events as (HLO text,
    start, end)."""
    mine, others = [], []
    for n, ivs in selfs:
        (mine if is_collective(n) else others).extend(ivs)
    spans = mine + _clip([(s, e) for n, s, e in async_ops
                          if is_collective(n)], lo, hi)
    if not spans:
        return 0.0, 0.0
    exposed = _subtract(_union(mine), _union(others))
    return float(_length(_union(spans))), float(_length(exposed))


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    modules: dict[str, tuple[float, int]]      # name -> (seconds, count)
    top_ops: list[tuple[str, float]]
    idle_gaps: list[tuple[str, float]]
    collective_s: float = 0.0
    collective_exposed_s: float = 0.0

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

    return reduce_planes(ProfileData.from_file(path).planes, path, top=top)


def reduce_planes(planes, path: str = "trace", *, top: int = 10) -> Reduced:
    """``reduce`` over planes as ``ProfileData`` gives them: each with a
    ``name`` and ``lines``, each line with a ``name`` and ``events``, each
    event with a ``name``, ``start_ns``, ``end_ns`` and ``duration_ns``."""
    devices, hosts = [], []
    window = None
    for plane in planes:
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
        async_ops = ([(e.name, e.start_ns, e.end_ns)
                      for e in lines["Async XLA Ops"].events]
                     if "Async XLA Ops" in lines else [])
        per_dev.append((ops, mods, async_ops))
    if window is None:
        starts = [s for ops, _, _ in per_dev for _, s, _ in ops]
        ends = [e for ops, _, _ in per_dev for _, _, e in ops]
        if not starts:
            raise ValueError(f"{path}: no device operation in the trace")
        window = (min(starts), max(ends))
    lo, hi = window
    window_s = (hi - lo) * 1e-9

    busy_total = coll_total = exposed_total = 0.0
    modules: dict[str, list] = {}
    op_time: dict[str, float] = {}
    gaps = []
    for i, (ops, mods, async_ops) in enumerate(per_dev):
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy) * 1e-9
        inside = [(n, s, e) for n, s, e in ops if s >= lo and e <= hi]
        selfs = _self_intervals(inside)
        for name, ivs in selfs:
            key = op_label(name)
            op_time[key] = op_time.get(key, 0.0) + _length(ivs) * 1e-9
        coll, exposed = collective_times(selfs, async_ops, lo, hi)
        coll_total += coll * 1e-9
        exposed_total += exposed * 1e-9
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
        idle_gaps=labelled, collective_s=coll_total / n,
        collective_exposed_s=exposed_total / n)
