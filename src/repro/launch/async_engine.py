"""Async disaggregated serving loop over the tick engines (DESIGN.md §14).

``ServeEngine``/``PagedServeEngine`` already split a decode tick into a
device dispatch (``_dispatch_tick`` — jit calls only) and a host harvest
(``_harvest`` — materialize emitted tokens, retire finished slots).  This
module threads that seam: a **scheduler** thread owns every engine-state
mutation (admission, preemption/resume, tick dispatch, harvest
bookkeeping), while a **drain** thread does nothing but materialize
emitted-token device buffers to host (``np.asarray`` — the detokenize-side
work), so device dispatch never blocks on host materialization.  Requests
stream in through :meth:`submit` and stream out through :meth:`results`;
the trace-at-once :meth:`run` survives as a thin compatibility wrapper
with the tick-loop engines' arrival semantics.

Queue topology (bounded, single producer/consumer on every edge)::

    caller --submit_q--> scheduler --drain_q--> drain --harvest_q--> scheduler
                                                            (applies _harvest)
    scheduler --results_q--> caller (results()/run())

**Why tokens stay bit-identical to the tick loop.**  The pipeline changes
*when* host code looks at a tick's results, never what the device
computes: per-request sampling folds only (request seed, position), spec
acceptance folds the verified position, and preempt/resume round-trips are
bit-exact — the repo-wide schedule-invariance contract.  Three ordering
rules keep the host bookkeeping equally exact:

* every dispatched tick's ``active`` snapshot rides in a freshly allocated
  buffer (``_snap_fn``), so later ticks donating the live state cannot
  invalidate what the drain thread reads;
* all in-flight ticks are harvested (pipeline flush) before any admission,
  resume, or preemption — a freed slot is reused, or a victim chosen, only
  after the scheduler has seen every earlier tick's finishes;
* speculative ticks are already host-synchronous in the engine (draft
  metering + the acceptance EWMA feed the fidelity ladder each tick), so
  they enter the drain queue pre-materialized and the pipeline depth
  degrades gracefully to admission-vs-decode overlap.

Telemetry: the caller's thread emits a "submit" event, the scheduler
thread every event, record and span the tick loop would plus an
"admission" span around each admission pass with its "flush" (the wait
for in-flight ticks), and the drain thread only closes "drain" spans
(the trace is lock-guarded for exactly this cross-thread writer).  A
decode tick's "decode_block" span opens at its dispatch on the scheduler
and closes at its harvest there; its "drain" child is parented across
threads by id.
"""
from __future__ import annotations

import collections
import queue
import threading
import time

import numpy as np

from ..obs import span
from .engine import Completion, Request

_STOP = object()


class AsyncServeEngine:
    """Streaming wrapper running a serve engine on a background pipeline.

    Wraps an already-constructed ``ServeEngine``/``PagedServeEngine``
    (any configuration: paged, speculative, sharded, spill/priority,
    telemetry, AOT prefill buckets) without touching its jits or state
    layout.  Exactly one scheduler thread mutates the engine; public
    methods only exchange messages with it, so ``submit`` is safe from
    any thread.  ``results()``/``run()`` assume a single consumer.

    Threads start lazily on first use and idle between traces, so one
    wrapper (and its warmed engine) serves many runs; they are daemons,
    and :meth:`close` shuts them down deterministically.
    """

    def __init__(self, engine, *, drain_depth: int = 4,
                 poll_s: float = 0.02):
        if drain_depth < 1:
            raise ValueError("drain_depth >= 1 (1 disables pipelining)")
        self.engine = engine
        self.drain_depth = drain_depth
        self._poll_s = poll_s
        self._submit_q: queue.Queue = queue.Queue()
        self._drain_q: queue.Queue = queue.Queue()
        self._harvest_q: queue.Queue = queue.Queue()
        self._results_q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._pending: set[int] = set()       # submitted, not yet finished
        self._error: BaseException | None = None
        self._closing = False
        self._started = False
        self._sched_t: threading.Thread | None = None
        self._drain_t: threading.Thread | None = None
        # host-visible pipeline counters (the engine's registry exposes
        # them as a lazy group — same pattern as pool/spec/fidelity)
        self._submitted = 0
        self._completed = 0
        self._dispatched_ticks = 0
        self._flushes = 0
        self._max_inflight = 0
        engine.metrics.register_group("async", self._async_stats)

    # -- public API --------------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue one request (thread-safe).  Validation errors raise
        here, on the caller; scheduler-side failures surface on the next
        ``submit``/``results``/``run`` call."""
        self._check_error()
        with self._lock:
            if req.rid in self._pending:
                raise ValueError(
                    f"request {req.rid}: rid already in flight")
            # static shape/range validation on the caller thread — the
            # engine-state part (duplicate in-flight rid) is the pending
            # set above, which the scheduler cannot race
            self.engine._validate(req)
            self._pending.add(req.rid)
            self._submitted += 1
        self._start()
        tel = self.engine.telemetry
        if tel is not None:
            tel.event("submit", self.engine.tick, rid=req.rid)
        self._submit_q.put(req)

    def results(self, *, timeout: float | None = None):
        """Yield completions as the pipeline finishes them; returns when
        nothing submitted remains pending.  ``timeout`` bounds the total
        wait for the *next* completion (None = wait forever)."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            self._check_error()
            try:
                comp = self._results_q.get(timeout=self._poll_s)
            except queue.Empty:
                with self._lock:
                    drained = not self._pending
                if drained:
                    # completions enqueue before the pending rid clears,
                    # so an empty pending set means the queue has all of
                    # them — one final non-blocking sweep
                    while True:
                        try:
                            yield self._results_q.get_nowait()
                        except queue.Empty:
                            return
                if deadline is not None and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"no completion within {timeout}s "
                        f"({len(self._pending)} pending)")
                continue
            deadline = (None if timeout is None
                        else time.monotonic() + timeout)
            yield comp

    def run(self, requests: list[Request]) -> list[Completion]:
        """Tick-loop-compatible trace serve: submit everything (arrival
        ticks respected by the scheduler exactly like ``ServeEngine.run``),
        block until all of it finished, return completions sorted by rid.
        The wrapper stays live for further runs."""
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError(f"duplicate rids in one trace: {rids}")
        for r in sorted(requests, key=lambda r: r.arrival):
            self.submit(r)
        out, expect = [], set(rids)
        for comp in self.results():
            if comp.rid in expect:
                expect.discard(comp.rid)
                out.append(comp)
            if not expect:
                break
        if expect:
            self._check_error()
            raise RuntimeError(f"pipeline drained with {sorted(expect)} "
                               f"unfinished")
        return sorted(out, key=lambda c: c.rid)

    def close(self) -> None:
        """Drain outstanding work, stop both threads, re-raise any
        pipeline error.  Idempotent."""
        self._closing = True
        if self._sched_t is not None:
            self._sched_t.join()
            self._sched_t = None
        if self._drain_t is not None:
            self._drain_q.put(_STOP)
            self._drain_t.join()
            self._drain_t = None
        self._started = False
        self._check_error()

    # engine passthroughs the harness and benches read
    @property
    def tick(self) -> int:
        return self.engine.tick

    @property
    def telemetry(self):
        return self.engine.telemetry

    @property
    def metrics(self):
        return self.engine.metrics

    def _async_stats(self) -> dict:
        return {"submitted": self._submitted,
                "completed": self._completed,
                "dispatched_ticks": self._dispatched_ticks,
                "pipeline_flushes": self._flushes,
                "max_inflight": self._max_inflight,
                "drain_depth": self.drain_depth}

    # -- plumbing ----------------------------------------------------------

    def _check_error(self) -> None:
        if self._error is not None:
            raise RuntimeError(
                "async serve pipeline failed") from self._error

    def _start(self) -> None:
        with self._lock:
            if self._started:
                return
            self._started = True
        self._closing = False
        self._error = None
        self._drain_t = threading.Thread(
            target=self._drain_loop, name="nldpe-drain", daemon=True)
        self._sched_t = threading.Thread(
            target=self._scheduler_loop, name="nldpe-sched", daemon=True)
        self._drain_t.start()
        self._sched_t.start()

    def _finish(self, comp: Completion) -> None:
        self._completed += 1
        self._results_q.put(comp)
        with self._lock:
            self._pending.discard(comp.rid)

    # -- drain thread: device -> host materialization only -----------------

    def _drain_loop(self) -> None:
        tel = self.engine.telemetry
        while True:
            item = self._drain_q.get()
            if item is _STOP:
                return
            emits, active, tick = item
            try:
                with span(tel, "drain", self.engine.tick,
                          parent=tick and tick.id):
                    e = np.asarray(emits)
                    a = np.asarray(active)
                self._harvest_q.put((e, a, tick))
            except BaseException as exc:          # forward, never die silent
                self._harvest_q.put(exc)

    # -- scheduler thread: the only engine-state mutator --------------------

    def _apply_harvests(self, down_to: int) -> None:
        """Apply drained ticks to the engine, blocking until at most
        ``down_to`` dispatched ticks remain un-harvested.  ``down_to=0``
        is the pipeline flush that must precede every admission, resume,
        or preemption decision.

        Completions are handed out at the end.  Once a harvest leaves no
        slot active, the ticks still in flight emit nothing and are
        harvested first, so a caller holding its last completion finds
        the pipeline empty and may drive the engine itself."""
        done = []
        while self._inflight:
            if self._inflight > down_to:
                item = self._harvest_q.get()
            else:
                try:
                    item = self._harvest_q.get_nowait()
                except queue.Empty:
                    break
            self._inflight -= 1
            if isinstance(item, BaseException):
                raise item
            done += self.engine._collect(*item)
            if done and not self.engine.any_active:
                down_to = 0
        for comp in done:
            self._finish(comp)

    def _flush(self) -> None:
        if self._inflight:
            self._flushes += 1
            self._apply_harvests(0)

    def _scheduler_loop(self) -> None:
        try:
            self._inflight = 0
            self._serve()
            self._apply_harvests(0)
        except BaseException as exc:
            self._error = exc
            # unblock any consumer: pending rids will never finish
            with self._lock:
                self._pending.clear()
        finally:
            self._started = False

    def _serve(self) -> None:
        eng = self.engine
        tel = eng.telemetry
        arrivals: list[Request] = []          # sorted by arrival
        waiting = collections.deque()
        while True:
            # ingest new submissions (non-blocking)
            while True:
                try:
                    r = self._submit_q.get_nowait()
                except queue.Empty:
                    break
                i = 0
                while i < len(arrivals) and arrivals[i].arrival <= r.arrival:
                    i += 1
                arrivals.insert(i, r)
            # opportunistically apply whatever the drain thread finished
            self._apply_harvests(self._inflight)

            progressed = False
            while arrivals and arrivals[0].arrival <= eng.tick:
                r = arrivals.pop(0)
                if tel is not None:
                    tel.enqueue(r.rid, r.arrival)
                waiting.append(r)
                progressed = True

            # admission / resume / preemption all require a fully
            # harvested engine (see module docstring); only pay the flush
            # when one of them can actually happen
            if eng._preempted or (waiting and eng._can_admit(waiting)):
                with span(tel, "admission", eng.tick):
                    with span(tel, "flush", eng.tick):
                        self._flush()
                    n_pre = len(eng._preempted)
                    eng._resume_preempted(waiting)
                    progressed |= len(eng._preempted) != n_pre
                    if waiting and eng._can_admit(waiting):
                        wave = eng._select_wave(waiting)
                        if wave:
                            for comp in eng._admit_wave(wave):
                                self._finish(comp)
                            progressed = True

            if eng.any_active:
                if self._inflight >= self.drain_depth:
                    self._apply_harvests(self.drain_depth - 1)
                    continue
                out = eng._dispatch_tick()
                self._inflight += 1
                self._max_inflight = max(self._max_inflight,
                                         self._inflight)
                self._dispatched_ticks += 1
                self._drain_q.put(out)
                continue

            # nothing active on device
            if self._inflight:
                self._apply_harvests(0)
                continue
            if progressed:
                continue
            if arrivals:                      # idle until the next arrival
                eng.tick = max(eng.tick, arrivals[0].arrival)
                continue
            if waiting or eng._preempted:
                raise RuntimeError(
                    f"scheduler deadlock: {len(waiting)} waiting and "
                    f"{len(eng._preempted)} preempted request(s), no "
                    f"active slots, no future arrivals, and admission "
                    f"made no progress (admission blocked or the pool "
                    f"is too small for the requests)")
            # fully idle: wait for work or shutdown
            if self._closing:
                return
            try:
                r = self._submit_q.get(timeout=self._poll_s)
            except queue.Empty:
                continue
            i = 0
            while i < len(arrivals) and arrivals[i].arrival <= r.arrival:
                i += 1
            arrivals.insert(i, r)
