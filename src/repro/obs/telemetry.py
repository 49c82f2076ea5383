"""Per-request latency tracing + structured serve-event trace (DESIGN.md §12).

Three cooperating pieces, all host-side and allocation-bounded:

* :class:`EventTrace` — a ring buffer of typed events with a committed
  schema (:data:`EVENT_SCHEMA`).  Every event the engines emit — request
  lifecycle edges, COW forks, evictions, fidelity-ladder transitions,
  compiles — is one dict validated against the schema at emit time and
  flushable as JSONL.  The buffer is a ``deque(maxlen=capacity)``: a
  week-long serve cannot grow it, old events fall off the far end and are
  *counted*, never silently lost.

* **Spans** — the engine's phases (scheduler pass, admission wave and its
  parts, decode tick with its dispatch, drain and harvest, the draft/verify
  split) are events of the same trace that also carry their start ``t0``,
  an id ``span`` and the ``parent`` span's id.  A same-thread span is a
  context manager (:meth:`EventTrace.span`) whose parent is the innermost
  open span on its thread, and it is entered as a
  ``jax.profiler.TraceAnnotation("nldpe.<kind>")`` at the same two
  boundaries, so a profile shows the program's spans on the profiler's own
  clock beside the device ops.  The decode tick, dispatched on one thread
  and materialized on another, is an :meth:`EventTrace.open` /
  :meth:`EventTrace.close` pair.  Closed spans fold into per-kind lifetime
  totals (``Telemetry.summary()["phases"]``).  **Sync discipline**: a span
  closes only where the engine already synchronizes or does host work;
  telemetry never adds a device sync, so the async dispatch pipeline is
  unchanged and the enabled-vs-disabled token streams stay bit-identical.

* :class:`RequestRecord` + :class:`Percentiles` — the per-request
  lifecycle (enqueue → admit → first token → finish) measured on
  ``time.perf_counter`` (monotonic: an NTP step can never produce a
  negative phase) and on the engine's tick clock, yielding TTFT, TPOT,
  queue wait, pages held, and per-request speculative acceptance, folded
  into streaming p50/p90/p99 summaries.

:class:`TickProfiler` is the opt-in deep lens: capture N engine ticks with
``jax.profiler`` (perfetto-viewable trace) and stop — serving continues.

The accumulators (:class:`BoundedLog`, :class:`Percentiles`, the span
totals) are internally locked: the async serve pipeline (DESIGN.md §14)
has its drain thread close "drain" spans while the scheduler thread owns
every other write, and a reader may snapshot mid-serve.  The locks bound
tiny host-side critical sections — never a device sync — so the
zero-behavioral-footprint bar is untouched.

Nothing here imports from ``launch`` (the engines import *us*), and jax is
imported lazily (the profiler, the annotations, the compile listener), so
the module stays importable without it.
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import threading
import time
import weakref
from collections import deque

import numpy as np

# ---------------------------------------------------------------------------
# committed event schema
# ---------------------------------------------------------------------------

#: Version stamp written into every JSONL flush; bump on any field change.
#: v2: hierarchical-cache events — "spill" reshaped from its reserved
#: placeholder to per-page, plus "restore"/"preempt"/"resume".
#: v3: spans — phase kinds carry :data:`SPAN_FIELDS` in place of their
#: ``wall_s`` payload (duration is ``t - t0``); new kinds "submit",
#: "compile" and the spans "admission", "flush", "plan", "prefill",
#: "first_token_sync", "merge", "dispatch", "drain", "harvest".
#: v4: "decode_block" carries ``kv_blocks_read``/``kv_blocks_view``, the
#: pages paged attention reads in the tick and the pages a dense view
#: would gather (None where attention does not read pages in place).
SCHEMA_VERSION = 4

#: Fields every span carries besides its payload: its start (``t`` is its
#: end, both on the trace clock), its id, and the id of the span that
#: encloses it (None at the root).
SPAN_FIELDS = ("t0", "span", "parent")


def _span(*payload: str) -> tuple[str, ...]:
    return SPAN_FIELDS + payload


#: Committed schema: event kind -> exactly these payload fields (every
#: event additionally carries the BASE_FIELDS).  ``emit`` validates the
#: field *set* — a call site cannot drift from the schema unnoticed, and a
#: consumer can rely on every field being present.
EVENT_SCHEMA: dict[str, tuple[str, ...]] = {
    # request lifecycle edges; "submit" is the async front end's caller
    # thread, "enqueue" the scheduler's ingest of the request
    "submit": ("rid",),
    "enqueue": ("rid",),
    "admit": ("rid", "slot", "prompt_len", "reuse", "queue_wait_ticks"),
    "first_token": ("rid",),
    "finish": ("rid", "reason", "n_tokens", "ttft_s", "tpot_s",
               "queue_wait_s", "pages_held", "drafted", "accepted"),
    # scheduler spans: one admission pass (flush, resume, wave), the wait
    # for in-flight ticks, and the wave with its host plan, chunk
    # dispatches, first-token sync and decode-state merge
    "admission": _span(),
    "flush": _span(),
    "admission_wave": _span("rids", "n_reqs", "n_chunks"),
    "plan": _span(),
    "prefill": _span(),
    "first_token_sync": _span(),
    "merge": _span(),
    # decode tick (cross-thread: dispatch -> harvest) and its parts;
    # harvest carries each request's tokens received (emission stamps)
    "decode_block": _span("n_active", "block", "kv_blocks_read",
                          "kv_blocks_view"),
    "dispatch": _span(),
    "drain": _span(),
    "harvest": _span("rids", "n_emitted"),
    "spec_draft": _span("k", "n_active"),
    "spec_verify": _span("k", "drafted", "accepted"),
    # a jax compile stage, parented to the innermost open span
    "compile": ("stage", "fun", "seconds", "parent"),
    # paged-pool lifecycle
    "cow_fork": ("src", "dst"),          # src == -1: forked off a host
    #                                      payload (spilled boundary page)
    "eviction": ("page",),
    # hierarchical cache (DESIGN.md §13): device→host page demotion,
    # host→device promotion, and priority preempt/resume swaps
    "spill": ("page",),
    "restore": ("page",),
    "preempt": ("rid", "slot", "pages", "priority"),
    "resume": ("rid", "slot", "pages"),
    # closed-loop fidelity ladder transitions (DESIGN.md §10)
    "fidelity": ("kind", "spec_k", "ewma", "vclock_s"),
}

_SCHEMA_KEYS = {ev: frozenset(f) for ev, f in EVENT_SCHEMA.items()}
_ANNOTATIONS = {ev: f"nldpe.{ev}" for ev in EVENT_SCHEMA}

#: Fields every event carries: kind, wall timestamp (perf_counter), the
#: engine tick it was observed at, and a monotone sequence number (gaps
#: after a flush reveal ring overwrites).
BASE_FIELDS = ("ev", "t", "tick", "seq")

#: Prefix of the ``jax.monitoring`` duration events of a compile.
COMPILE_EVENTS = "/jax/core/compile/"


class BoundedLog:
    """A ring buffer that counts what it drops.

    The shared bounding policy for every unbounded-growth log in the serve
    path (the event trace here, the fidelity ladder's event log): a
    ``deque(maxlen=capacity)`` plus a ``dropped`` counter, so a multi-day
    serve holds memory constant while the telemetry stream still records
    *that* (and how much) history was lost.
    """

    def __init__(self, capacity: int = 4096):
        if capacity < 1:
            raise ValueError(f"BoundedLog capacity={capacity} must be >= 1")
        self.capacity = capacity
        self._items: deque = deque(maxlen=capacity)
        self.dropped = 0
        self._lock = threading.RLock()   # re-entrant: EventTrace.emit
        #                                  holds it across seq-stamp+append

    def append(self, item) -> None:
        with self._lock:
            if len(self._items) == self.capacity:
                self.dropped += 1
            self._items.append(item)

    def __len__(self) -> int:
        return len(self._items)

    def __iter__(self):
        # iterate a point-in-time copy: a concurrent append to a full ring
        # mutates both ends and would invalidate a live deque iterator
        with self._lock:
            return iter(list(self._items))

    def __getitem__(self, i):
        with self._lock:
            return self._items[i]

    def clear(self) -> None:
        with self._lock:
            self._items.clear()
            self.dropped = 0


_TOP = object()          # span parent: the innermost open span on the thread
_annotation = None       # jax.profiler.TraceAnnotation, imported on first use


class Span:
    """One span while it is open; ``span[field] = value`` fills a payload
    field known only later (a wave's chunk count).  Use it through
    :meth:`EventTrace.span` (same thread, a context manager) or
    :meth:`EventTrace.open` / :meth:`EventTrace.close` (across threads)."""

    __slots__ = ("_trace", "ev", "tick", "fields", "id", "parent", "t0",
                 "_ann")

    def __init__(self, trace: "EventTrace", ev: str, tick: int, parent,
                 fields: dict):
        self._trace = trace
        self.ev = ev
        self.tick = tick
        self.fields = fields
        self.id = next(trace._ids)
        self.parent = parent
        self.t0 = 0.0
        self._ann = None

    def __setitem__(self, field: str, value) -> None:
        self.fields[field] = value

    def __enter__(self) -> "Span":
        global _annotation
        stack = self._trace._stack()
        if self.parent is _TOP:
            self.parent = stack[-1].id if stack else None
        stack.append(self)
        if _annotation is None:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        self._ann = _annotation(_ANNOTATIONS[self.ev])
        self._ann.__enter__()
        self.t0 = self._trace._clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t = None if exc_type is not None else self._trace._clock()
        self._ann.__exit__(exc_type, exc, tb)
        self._trace._stack().pop()
        if t is not None:                # a failed phase records nothing
            self._trace._close(self, t)
        return False


class _NullSpan:
    """What :func:`span` hands out with telemetry off: a no-op."""

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def __setitem__(self, field: str, value) -> None:
        pass


NULL_SPAN = _NullSpan()


def span(tel: "Telemetry | None", ev: str, tick: int, **fields):
    """``tel.trace.span(...)``, or a no-op when telemetry is off — so an
    engine call site is one ``with`` and no guard."""
    if tel is None:
        return NULL_SPAN
    return tel.trace.span(ev, tick, **fields)


class EventTrace(BoundedLog):
    """Bounded structured trace of typed serve events and spans.

    ``emit`` validates the payload field set against :data:`EVENT_SCHEMA`
    (exact match — missing and extra fields both raise: the schema is a
    contract, not a suggestion) and stamps the base fields.  Spans add
    :data:`SPAN_FIELDS` themselves and are emitted when they close.
    """

    def __init__(self, capacity: int = 4096,
                 clock=time.perf_counter):
        super().__init__(capacity)
        self._clock = clock
        self._seq = 0
        self._ids = itertools.count()
        self._local = threading.local()
        self._totals: dict[str, list] = {}      # kind -> [seconds, calls]

    def emit(self, ev: str, tick: int, **fields) -> dict:
        return self._emit(ev, tick, None, fields)

    def _emit(self, ev: str, tick: int, t, fields: dict,
              t0: float | None = None) -> dict:
        """Validate and append one event; ``t`` None stamps it now, and a
        span's ``t0`` folds ``t - t0`` into its kind's totals."""
        want = _SCHEMA_KEYS.get(ev)
        if want is None:
            raise ValueError(f"unknown event kind {ev!r} "
                             f"(EVENT_SCHEMA has {sorted(EVENT_SCHEMA)})")
        if fields.keys() != want:
            raise ValueError(
                f"event {ev!r} fields {sorted(fields)} != schema "
                f"{sorted(want)}")
        with self._lock:
            rec = {"ev": ev, "t": self._clock() if t is None else t,
                   "tick": int(tick), "seq": self._seq, **fields}
            self._seq += 1
            self.append(rec)
            if t0 is not None:
                tot = self._totals.setdefault(ev, [0.0, 0])
                tot[0] += rec["t"] - t0
                tot[1] += 1
        return rec

    # -- spans -------------------------------------------------------------

    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current(self):
        """Id of the innermost span open on the calling thread, or None."""
        stack = self._stack()
        return stack[-1].id if stack else None

    def span(self, ev: str, tick: int, *, parent=_TOP, **fields) -> Span:
        """A same-thread span: ``with trace.span(kind, tick, ...) as sp``.
        Its parent is the innermost span open on this thread unless
        ``parent`` names one (a child of a cross-thread span)."""
        return Span(self, ev, tick, parent, fields)

    def open(self, ev: str, tick: int, *, parent=None, **fields) -> Span:
        """Open a span that another thread may close (:meth:`close`); it
        is not on any thread's stack and has no profiler annotation."""
        sp = Span(self, ev, tick, parent, fields)
        sp.t0 = self._clock()
        return sp

    def close(self, sp: Span) -> dict:
        return self._close(sp, self._clock())

    def _close(self, sp: Span, t: float) -> dict:
        return self._emit(sp.ev, sp.tick, t,
                          {"t0": sp.t0, "span": sp.id, "parent": sp.parent,
                           **sp.fields}, sp.t0)

    def totals(self) -> dict:
        """Lifetime ``{kind: {"seconds", "calls"}}`` of the closed spans."""
        with self._lock:
            return {k: {"seconds": s, "calls": n}
                    for k, (s, n) in self._totals.items()}

    def clear(self) -> None:
        with self._lock:
            super().clear()
            self._seq = 0
            self._totals.clear()

    def flush_jsonl(self, path) -> int:
        """Write the buffered events as JSON Lines: one meta record (schema
        version, drop count) followed by one line per event, oldest first.
        Returns the number of event lines written.  The buffer is left
        intact (flush is an observation too)."""
        with self._lock:
            events = list(self._items)
            dropped = self.dropped
        with open(path, "w") as f:
            meta = {"ev": "meta", "schema_version": SCHEMA_VERSION,
                    "events": len(events), "dropped": dropped}
            f.write(json.dumps(meta) + "\n")
            for rec in events:
                f.write(json.dumps(rec) + "\n")
        return len(events)


# ---------------------------------------------------------------------------
# streaming percentiles
# ---------------------------------------------------------------------------

class Percentiles:
    """Streaming percentile summary over a sliding observation window.

    Retains the most recent ``window`` observations exactly and computes
    percentiles with numpy's default linear interpolation — below the
    window size the summary is *exact* (asserted against ``np.percentile``
    in tests/test_telemetry.py), above it the summary covers the freshest
    ``window`` samples, which is the operationally useful statistic (a
    latency SLO cares about now, not the lifetime average).  ``count`` and
    ``total`` keep lifetime accounting either way.
    """

    QUANTILES = (50.0, 90.0, 99.0)

    def __init__(self, window: int = 4096):
        if window < 1:
            raise ValueError(f"Percentiles window={window} must be >= 1")
        self.window = window
        self._vals: deque[float] = deque(maxlen=window)
        self.count = 0
        self.total = 0.0
        self._lock = threading.Lock()

    def add(self, v: float) -> None:
        v = float(v)
        with self._lock:
            self._vals.append(v)
            self.count += 1
            self.total += v

    def summary(self) -> dict:
        """{count, mean, max, p50, p90, p99} — None-filled when empty."""
        with self._lock:
            if not self._vals:
                return {"count": 0, "mean": None, "max": None,
                        **{f"p{int(q)}": None for q in self.QUANTILES}}
            arr = np.asarray(self._vals, dtype=np.float64)
            count, total = self.count, self.total
        out = {"count": count,
               "mean": float(total / count),
               "max": float(arr.max())}
        ps = np.percentile(arr, self.QUANTILES)
        for q, p in zip(self.QUANTILES, ps):
            out[f"p{int(q)}"] = float(p)
        return out

    def reset(self) -> None:
        with self._lock:
            self._vals.clear()
            self.count = 0
            self.total = 0.0


# ---------------------------------------------------------------------------
# request lifecycle
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RequestRecord:
    """One request's lifecycle timestamps (perf_counter wall + engine
    ticks) and footprint counters.  Derived latencies are ``None`` until
    the corresponding edge has happened."""

    rid: int
    enqueue_s: float
    enqueue_tick: int
    admit_s: float | None = None
    admit_tick: int | None = None
    first_token_s: float | None = None
    finish_s: float | None = None
    finish_tick: int | None = None
    prompt_len: int = 0
    reuse: int = 0                  # radix-hit prompt positions (paged)
    n_tokens: int = 0
    reason: str | None = None
    pages_held: int = 0
    drafted: int = 0                # speculative drafts during tenure
    accepted: int = 0

    @property
    def queue_wait_s(self) -> float | None:
        return None if self.admit_s is None else self.admit_s - self.enqueue_s

    @property
    def queue_wait_ticks(self) -> int | None:
        return (None if self.admit_tick is None
                else self.admit_tick - self.enqueue_tick)

    @property
    def ttft_s(self) -> float | None:
        """Enqueue -> first generated token (the first token is sampled at
        the end of the request's admission wave)."""
        return (None if self.first_token_s is None
                else self.first_token_s - self.enqueue_s)

    @property
    def tpot_s(self) -> float | None:
        """Mean wall seconds per generated token after the first."""
        if self.finish_s is None or self.first_token_s is None:
            return None
        if self.n_tokens <= 1:
            return 0.0
        return (self.finish_s - self.first_token_s) / (self.n_tokens - 1)

    @property
    def acceptance(self) -> float | None:
        return None if self.drafted == 0 else self.accepted / self.drafted


# ---------------------------------------------------------------------------
# the engine-facing facade
# ---------------------------------------------------------------------------

def _compile_listener(ref):
    """A ``jax.monitoring`` duration listener feeding the telemetry behind
    the weak reference ``ref``: jax keeps its listeners for the life of
    the process, and a listener must not keep a dropped telemetry alive."""
    def on_event(event: str, duration: float, **kw) -> None:
        tel = ref()
        if tel is not None and event.startswith(COMPILE_EVENTS):
            tel._compiled(event, duration, kw.get("fun_name", ""))
    return on_event


class Telemetry:
    """Facade the serve engines drive: event trace and spans + lifecycle
    records + latency percentile accumulators + a compile counter, behind
    one object so an engine call site is a single
    ``if self.telemetry is not None`` guard (or one :func:`span`).

    Everything is host-side observation — no method here may dispatch
    device work or change what the engine computes.  The bit-identity
    contract (telemetry on == off, token for token) is asserted across the
    full differential matrix in tests/test_engine_differential.py.
    """

    def __init__(self, *, capacity: int = 4096,
                 percentile_window: int = 4096,
                 record_capacity: int = 4096,
                 profile_ticks: int = 0,
                 profile_dir: str | None = None,
                 clock=time.perf_counter):
        self._clock = clock
        self.trace = EventTrace(capacity, clock=clock)
        self.ttft = Percentiles(percentile_window)
        self.tpot = Percentiles(percentile_window)
        self.queue_wait = Percentiles(percentile_window)
        self.live: dict[int, RequestRecord] = {}
        self.records = BoundedLog(record_capacity)   # finished lifecycles
        self.profiler = (TickProfiler(profile_dir, profile_ticks)
                         if profile_ticks > 0 else None)
        self._counters: dict[str, int] = {
            "requests_enqueued": 0, "requests_finished": 0,
            "tokens_emitted": 0, "ticks": 0, "compiles": 0}
        self._tick = 0
        # every jax compile stage while this telemetry lives, parented to
        # the span open on the compiling thread ("which step recompiled")
        from jax import monitoring
        self._listener = _compile_listener(weakref.ref(self))
        monitoring.register_event_duration_secs_listener(self._listener)

    # -- request lifecycle ------------------------------------------------

    def enqueue(self, rid: int, tick: int) -> None:
        if rid in self.live:                 # engine validation rejects
            return                           # dup rids; stay silent here
        self.live[rid] = RequestRecord(rid=rid, enqueue_s=self._clock(),
                                       enqueue_tick=int(tick))
        self._counters["requests_enqueued"] += 1
        self.trace.emit("enqueue", tick, rid=rid)

    def admit(self, rid: int, tick: int, *, slot: int, prompt_len: int,
              reuse: int = 0, pages_held: int = 0) -> None:
        rec = self.live.get(rid)
        if rec is None:                      # direct _admit_wave drivers
            self.enqueue(rid, tick)          # (bench probes): synthesize
            rec = self.live[rid]
        rec.admit_s = self._clock()
        rec.admit_tick = int(tick)
        rec.prompt_len = int(prompt_len)
        rec.reuse = int(reuse)
        rec.pages_held = int(pages_held)
        self.trace.emit("admit", tick, rid=rid, slot=int(slot),
                        prompt_len=int(prompt_len), reuse=int(reuse),
                        queue_wait_ticks=rec.queue_wait_ticks)

    def first_token(self, rid: int, tick: int) -> None:
        rec = self.live.get(rid)
        if rec is None or rec.first_token_s is not None:
            return
        rec.first_token_s = self._clock()
        self.trace.emit("first_token", tick, rid=rid)

    def finish(self, rid: int, tick: int, *, reason: str, n_tokens: int,
               drafted: int = 0, accepted: int = 0) -> None:
        rec = self.live.pop(rid, None)
        if rec is None:
            return
        rec.finish_s = self._clock()
        rec.finish_tick = int(tick)
        rec.reason = reason
        rec.n_tokens = int(n_tokens)
        rec.drafted = int(drafted)
        rec.accepted = int(accepted)
        self.records.append(rec)
        self._counters["requests_finished"] += 1
        self._counters["tokens_emitted"] += rec.n_tokens
        if rec.ttft_s is not None:
            self.ttft.add(rec.ttft_s)
        if rec.tpot_s is not None:
            self.tpot.add(rec.tpot_s)
        if rec.queue_wait_s is not None:
            self.queue_wait.add(rec.queue_wait_s)
        self.trace.emit("finish", tick, rid=rid, reason=reason,
                        n_tokens=rec.n_tokens, ttft_s=rec.ttft_s,
                        tpot_s=rec.tpot_s, queue_wait_s=rec.queue_wait_s,
                        pages_held=rec.pages_held, drafted=rec.drafted,
                        accepted=rec.accepted)

    # -- generic events + compiles ----------------------------------------

    def event(self, ev: str, tick: int, **fields) -> None:
        self.trace.emit(ev, tick, **fields)

    def _compiled(self, event: str, seconds: float, fun) -> None:
        """One compile stage (jaxpr trace, MLIR lowering, backend compile);
        ``compiles`` counts backend compiles, one per executable built or
        loaded from the persistent cache."""
        stage = event[len(COMPILE_EVENTS):].removesuffix("_duration")
        if stage == "backend_compile":
            with self.trace._lock:
                self._counters["compiles"] += 1
        self.trace.emit("compile", self._tick, stage=stage, fun=str(fun),
                        seconds=float(seconds), parent=self.trace.current())

    def tick_boundary(self, tick: int) -> None:
        """Called once at the top of every engine tick: counts ticks and
        drives the opt-in N-tick profiler window."""
        self._tick = int(tick)
        self._counters["ticks"] += 1
        if self.profiler is not None:
            self.profiler.tick()

    # -- summaries ---------------------------------------------------------

    def summary(self) -> dict:
        """The latency story in one dict: lifecycle and compile counters,
        TTFT / TPOT / queue-wait percentile summaries (seconds), and the
        lifetime ``{"seconds", "calls"}`` of each span kind."""
        return {**self._counters,
                "inflight": len(self.live),
                "records_dropped": self.records.dropped,
                "events_dropped": self.trace.dropped,
                "ttft_s": self.ttft.summary(),
                "tpot_s": self.tpot.summary(),
                "queue_wait_s": self.queue_wait.summary(),
                "phases": self.trace.totals()}

    def flush_jsonl(self, path) -> int:
        return self.trace.flush_jsonl(path)

    def reset(self) -> None:
        """Zero every accumulator (a bench/epoch boundary); the profiler —
        if any — keeps its window state."""
        self.trace.clear()
        self.ttft.reset()
        self.tpot.reset()
        self.queue_wait.reset()
        self.live.clear()
        self.records.clear()
        for k in self._counters:
            self._counters[k] = 0

    def close(self) -> None:
        """Stop a still-open profiler window and the compile listener.
        Idempotent."""
        if self.profiler is not None:
            self.profiler.stop()
        if self._listener is not None:
            from jax import monitoring
            monitoring.unregister_event_duration_listener(self._listener)
            self._listener = None


class TickProfiler:
    """Opt-in ``jax.profiler`` capture of the first N engine ticks.

    The trace starts at the first tick boundary after attach and stops N
    boundaries later; the resulting directory is loadable in perfetto (or
    TensorBoard's profile plugin).  jax is imported lazily so the rest of
    the telemetry stack stays importable as a pure host-side module.  A
    profiler that fails to start or stop raises: a run that asked for a
    trace must not end without one.
    """

    def __init__(self, logdir: str | None, n_ticks: int):
        if n_ticks < 1:
            raise ValueError(f"TickProfiler n_ticks={n_ticks} must be >= 1")
        self.logdir = logdir or "/tmp/nldpe_profile"
        self.n_ticks = n_ticks
        self._remaining = n_ticks
        self.active = False
        self.done = False

    def tick(self) -> None:
        if self.done:
            return
        if not self.active:
            import jax
            jax.profiler.start_trace(self.logdir)
            self.active = True
            return
        self._remaining -= 1
        if self._remaining <= 0:
            self.stop()

    def stop(self) -> None:
        if self.active:
            import jax
            self.active = False
            jax.profiler.stop_trace()
        self.done = True
