"""Step tracing, the cycle FLIGHT RECORDER, and Perfetto trace export.

reference: vendor/k8s.io/utils/trace (utiltrace.Trace) as used by the
scheduling cycle (core/generic_scheduler.go:147-202 — steps "Basic checks
done", "Snapshotting scheduler cache and node infos done", "Computing
predicates done", "Prioritizing done", logged when the cycle exceeds
100 ms) — SURVEY.md §5 keeps the same span structure and slow-cycle log.

On top of the reference's threshold log, this module is the structured
observability layer: every ``Trace`` carries a span id, parent linkage and
thread tag, and — when the flight recorder is ARMED — the full span tree
of each scheduling cycle (the eight PHASES that partition the serving
thread's cycle: pop -- with the previous cycle's TEARDOWN inside it as
a span of its own, who ran during it and its two children --, snapshot,
prefilter, tensorize, host-masks, dispatch, packed-readback with
device-wait attribution, commit; the utiltrace steps; preemption wave;
the per-pod BIND TABLE; recompile events fed by the sanitize watchdog,
and the queue depths at cycle start) lands in a lock-guarded ring buffer
of the last N cycles (``KUBETPU_FLIGHT_N``, default 64).  The ring
serializes to
the Chrome ``traceEvents`` JSON format (one pid per component, one tid
per thread, ``ph: "X"`` spans) loadable in Perfetto/chrome://tracing,
alongside the existing ``jax.profiler`` XPlane capture.

Bounded-memory contract: the recorder holds AT MOST ``capacity`` cycle
records (older ones are dropped and counted — see ``dropped()`` and the
``scheduler_flight_recorder_dropped_total`` metric) and at most
``KUBETPU_FLIGHT_SPANS`` (default 512) spans AND instant events per
cycle (excess is dropped per record and counted in ``span_drops`` /
``event_drops``).  DISARMED (the
default) the recorder is a strict no-op: ``Trace`` takes no lock,
allocates no record, and the serving loop skips the queue-depth read —
the hot path is byte-identical to the pre-recorder behavior.
"""

from __future__ import annotations

import array
import collections
import contextlib
import gc
import logging
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

LOG = logging.getLogger("kubetpu.trace")

SLOW_CYCLE_THRESHOLD = 0.1  # 100 ms (generic_scheduler.go:148 LogIfLong)

# Monotonic wall clock: perf_counter deltas anchored to the process's
# wall epoch, captured ONCE at import.  Every span/duration stamp in
# this module (and the scheduler's dispatch-deadline / device-wait
# domain) reads wallclock() instead of time.time(): an NTP step moves
# time.time() but not perf_counter, so a step mid-cycle used to corrupt
# device_wait_s and every span length (negative durations, bogus
# deadline trips).  The epoch anchor keeps the values wall-meaningful —
# Perfetto `ts` microseconds still line up with real time — while
# durations-by-subtraction stay strictly monotonic.
_WALL_EPOCH = time.time() - time.perf_counter()


def wallclock() -> float:
    """time.time()-compatible timestamp that can never run backwards
    (see _WALL_EPOCH).  Use for any pair of stamps whose DIFFERENCE is
    a duration."""
    return _WALL_EPOCH + time.perf_counter()

FLIGHT_ENV = "KUBETPU_FLIGHT"
FLIGHT_N_ENV = "KUBETPU_FLIGHT_N"
FLIGHT_SPANS_ENV = "KUBETPU_FLIGHT_SPANS"
DEFAULT_FLIGHT_N = 64
DEFAULT_FLIGHT_SPANS = 512

# SURVEY §5: keep jax.profiler traces alongside the host-side step spans.
# While a capture is active (capture_device_trace below), every PHASE of
# the serving thread's cycle opens ONE jax.profiler.TraceAnnotation named
# "Scheduling:<phase>" for exactly its own extent -- the phase that is
# OPEN, never two at once; the pop phase two, one after the other
# ("Scheduling:teardown", then "Scheduling:pop") -- so device-idle gaps
# can be charged to what the host was doing, and every cycle drops a
# CLOCK_ANNOTATION carrying wallclock(), which puts any flight-recorder
# stamp of any thread on the profiler's timeline (offset = event start -
# wallclock_s).
_PROFILE_ACTIVE = False
CYCLE_TRACE = "Scheduling"
CLOCK_ANNOTATION = "kubetpu.clock"


@contextlib.contextmanager
def capture_device_trace(log_dir: str, profiler_options=None):
    """Capture a jax.profiler trace (XPlane/TensorBoard format) for the
    enclosed serving activity — the TPU analog of the reference's pprof
    endpoints (DebuggingConfiguration.EnableProfiling, SURVEY §5).  The
    cycle's phases appear as TraceAnnotations inside the capture.
    profiler_options: a ``jax.profiler.ProfileOptions`` handed to
    ``start_trace`` (e.g. the Python tracer off for a serving-rate
    capture)."""
    global _PROFILE_ACTIVE
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir, profiler_options=profiler_options)
    _PROFILE_ACTIVE = True
    try:
        yield log_dir
    finally:
        _PROFILE_ACTIVE = False
        jax.profiler.stop_trace()


def _emit_clock(cycle: int) -> None:
    """One short host event whose metadata is this process's wallclock()
    at (within a microsecond of) the event's own start on the profiler's
    clock."""
    import jax
    with jax.profiler.TraceAnnotation(CLOCK_ANNOTATION,
                                      wallclock_s=wallclock(), cycle=cycle):
        pass


# --------------------------------------------------------------------- spans


class FlightSpan:
    """One recorded span: a node of a cycle's span tree."""

    __slots__ = ("span_id", "parent_id", "name", "thread", "t0", "t1",
                 "args")

    def __init__(self, span_id: int, parent_id: int, name: str,
                 thread: str, t0: float, t1: Optional[float] = None,
                 args: Optional[Dict[str, Any]] = None):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.thread = thread
        self.t0 = t0
        self.t1 = t1
        self.args = args if args is not None else {}

    def to_dict(self) -> Dict[str, Any]:
        return {"id": self.span_id, "parent": self.parent_id,
                "name": self.name, "thread": self.thread,
                "t0": round(self.t0, 6),
                "t1": round(self.t1 if self.t1 is not None else self.t0, 6),
                "args": dict(self.args)}


# columns of a CycleRecord's bind table
BIND_SUBMITTED, BIND_STARTED, BIND_DONE = 0, 1, 2
BIND_STAMPS = 3


class _NullSpan:
    """Reusable no-op context manager: the disarmed hot path allocates
    nothing and takes no lock."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


# thread-local stack of (CycleRecord, FlightSpan) for the spans currently
# OPEN on this thread: parents nested spans and routes recompile events
# (note_compile_event) to the right cycle.  Thread-local, so no lock.
_tls = threading.local()


def _span_stack() -> list:
    st = getattr(_tls, "spans", None)
    if st is None:
        st = []
        _tls.spans = st
    return st


class CycleRecord:
    """The span tree of ONE scheduling cycle, plus its BIND TABLE.
    Spans come from the serving thread and are few (the cycle's
    structure); the lists are lock-guarded and capped all the same
    (drops are counted, never silent).  What happens once per POD on
    other threads -- the binds -- does not go through spans: it lands in
    a preallocated table of three stamps a pod (submitted, started,
    done, on wallclock()), written by row index from whichever thread
    runs the bind.  No lock, no allocation: each cell has one writer,
    and a reader that races a bind in flight sees a 0.0 (row not yet
    complete), never a torn value."""

    def __init__(self, seq: int, label: str,
                 queue_depths: Optional[Dict[str, int]] = None,
                 fields: Optional[Dict[str, Any]] = None,
                 max_spans: int = DEFAULT_FLIGHT_SPANS):
        self.seq = seq
        self.label = label
        self.t0 = wallclock()
        self.t1: Optional[float] = None
        self.queue_depths = dict(queue_depths or {})
        self.meta: Dict[str, Any] = dict(fields or {})
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: List[FlightSpan] = []   # kubelint: guarded-by(_lock)
        self._events: List[Dict[str, Any]] = []  # kubelint: guarded-by(_lock)
        self._next_id = 1                    # kubelint: guarded-by(_lock)
        self.span_drops = 0                  # kubelint: guarded-by(_lock)
        self.event_drops = 0                 # kubelint: guarded-by(_lock)
        # bind table (alloc_binds): 3 doubles a pod, row = batch index
        self._bind_t: Optional[array.array] = None
        self._bind_thread: List[Optional[str]] = []

    # -- bind table ---------------------------------------------------------

    def alloc_binds(self, pods: int) -> None:
        """Size the bind table for a batch of ``pods`` (the commit loop
        calls this once, before the first submit)."""
        self._bind_t = array.array("d", bytes(8 * BIND_STAMPS * pods))
        self._bind_thread = [None] * pods

    def stamp_bind(self, row: int, which: int) -> None:
        """Stamp wallclock() into column ``which`` (BIND_SUBMITTED /
        BIND_STARTED / BIND_DONE) of the pod's row; BIND_STARTED also
        names the thread that runs the bind."""
        tbl = self._bind_t
        if tbl is None or not 0 <= row < len(self._bind_thread):
            return
        tbl[BIND_STAMPS * row + which] = wallclock()
        if which == BIND_STARTED:
            self._bind_thread[row] = threading.current_thread().name

    def stamp_binds(self, rows, which: int) -> None:
        """``stamp_bind(row, which)`` for each of ``rows`` with ONE
        reading of the clock: the rows of a batch start and end
        together (Scheduler._bind_batch)."""
        tbl = self._bind_t
        if tbl is None:
            return
        now = wallclock()
        name = (threading.current_thread().name
                if which == BIND_STARTED else None)
        n = len(self._bind_thread)
        for row in rows:
            if 0 <= row < n:
                tbl[BIND_STAMPS * row + which] = now
                if name is not None:
                    self._bind_thread[row] = name

    def bind_rows(self) -> List[Tuple[float, float, float, Optional[str]]]:
        """(submitted, started, done, thread) per pod of the batch, in
        batch order; zeros where the pod was never submitted (it did not
        place) or the stamp has not landed yet."""
        tbl = self._bind_t
        if tbl is None:
            return []
        return [(tbl[BIND_STAMPS * i], tbl[BIND_STAMPS * i + 1],
                 tbl[BIND_STAMPS * i + 2], th)
                for i, th in enumerate(self._bind_thread)]

    def bind_spans(self) -> List[FlightSpan]:
        """The COMPLETE rows of the bind table as ``bind`` spans (started
        -> done, on the thread that ran them, ``queued_s`` = started -
        submitted) for the span exports; ids continue past the recorded
        spans'."""
        with self._lock:
            next_id = self._next_id
        out = []
        for i, (sub, start, done, th) in enumerate(self.bind_rows()):
            if done > 0.0 and start > 0.0:
                out.append(FlightSpan(
                    next_id + i, 1, "bind", th or "", start, done,
                    args={"row": i, "queued_s": round(start - sub, 6)}))
        return out

    # -- recording ----------------------------------------------------------

    def begin_span(self, name: str, parent_id: int = 0,
                   t0: Optional[float] = None,
                   **args) -> Optional[FlightSpan]:
        """Open a span; returns None when the per-record cap is hit (the
        drop is counted)."""
        thread = threading.current_thread().name
        with self._lock:
            if len(self._spans) >= self.max_spans:
                self.span_drops += 1
                return None
            span = FlightSpan(self._next_id, parent_id, name, thread,
                              t0 if t0 is not None else wallclock(),
                              args=args or {})
            self._next_id += 1
            self._spans.append(span)
        return span

    @staticmethod
    def end_span(span: Optional[FlightSpan],
                 t1: Optional[float] = None) -> None:
        if span is not None:
            span.t1 = t1 if t1 is not None else wallclock()

    def record_span(self, name: str, t0: float, t1: float,
                    parent_id: int = 0, **args) -> Optional[FlightSpan]:
        """Record an already-finished span (e.g. a Trace.step interval)."""
        span = self.begin_span(name, parent_id=parent_id, t0=t0, **args)
        if span is not None:
            span.t1 = t1
        return span

    def event(self, name: str, parent_id: int = 0,
              ts: Optional[float] = None, **args) -> None:
        """Record an instant event (ph "i" in the Chrome export) — used
        for recompiles fed by the sanitize watchdog.  Capped like spans
        (a recompile storm must not balloon the record); drops count.
        ts: when it happened, where that is not now."""
        ev = {"name": name, "ts": ts if ts is not None else wallclock(),
              "parent": parent_id,
              "thread": threading.current_thread().name,
              "args": dict(args)}
        with self._lock:
            if len(self._events) >= self.max_spans:
                self.event_drops += 1
                return
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, parent_id: Optional[int] = None, **args):
        """Scoped span: pushes itself on the thread's open-span stack so
        nested spans (and recompile events) parent under it.  Yields the
        FlightSpan (or None past the span cap) so callers can attach args
        — e.g. the readback's device_wait_s — before exit."""
        stack = _span_stack()
        if parent_id is None:
            parent_id = (stack[-1][1].span_id
                         if stack and stack[-1][0] is self
                         and stack[-1][1] is not None else 0)
        sp = self.begin_span(name, parent_id=parent_id, **args)
        stack.append((self, sp))
        try:
            yield sp
        finally:
            stack.pop()
            self.end_span(sp)

    # -- introspection ------------------------------------------------------

    def spans(self) -> List[FlightSpan]:
        with self._lock:
            return list(self._spans)

    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            spans = [s.to_dict() for s in self._spans]
            events = [dict(e) for e in self._events]
            drops = self.span_drops
            ev_drops = self.event_drops
        return {"seq": self.seq, "label": self.label,
                "t0": round(self.t0, 6),
                "t1": round(self.t1 if self.t1 is not None else self.t0, 6),
                "queue_depths": dict(self.queue_depths),
                "meta": dict(self.meta),
                "span_drops": drops, "event_drops": ev_drops,
                "spans": spans, "events": events,
                # one row a pod of the batch, batch order (meta
                # batch_pods names them): [submitted, started, done,
                # thread]
                "binds": [[round(a, 6), round(b, 6), round(c, 6), th]
                          for a, b, c, th in self.bind_rows()]}


class FlightRecorder:
    """Lock-guarded ring buffer of the last N CycleRecords.

    Bounded-memory contract: at most ``capacity`` records x
    ``max_spans_per_cycle`` spans each are retained; overflow in either
    dimension drops (oldest cycle / newest span) and counts.  Reads
    (``cycles``/``to_dict``/``to_chrome_trace``) snapshot under the lock
    and serialize outside it."""

    def __init__(self, capacity: Optional[int] = None,
                 max_spans_per_cycle: Optional[int] = None):
        self.capacity = capacity or int(
            os.environ.get(FLIGHT_N_ENV, str(DEFAULT_FLIGHT_N)))
        self.max_spans_per_cycle = max_spans_per_cycle or int(
            os.environ.get(FLIGHT_SPANS_ENV, str(DEFAULT_FLIGHT_SPANS)))
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque()  # kubelint: guarded-by(_lock)
        self._dropped = 0    # kubelint: guarded-by(_lock)
        self._seq = 0        # kubelint: guarded-by(_lock)
        # interpreter accounting (note_interpreter): the last reading of
        # every live thread's CPU clock and when it was taken
        self._thread_cpu: Dict[threading.Thread, float] = {}  # kubelint: guarded-by(_lock)
        self._thread_cpu_t = 0.0    # kubelint: guarded-by(_lock)
        # running sums of the collector hook (_on_gc), which takes no
        # lock: collections never overlap, so each has one writer at a
        # time.  gc_other_s: pauses on threads with no phase or bind job
        # open; the _seen twins are what the cycles before took
        self.gc_other_s = 0.0
        self.gc_collections = 0
        self._gc_other_seen = 0.0   # kubelint: guarded-by(_lock)
        self._gc_seen = 0           # kubelint: guarded-by(_lock)
        # the heap policy's hand-offs (note_heap; utils/heap.py): None
        # until a serving scheduler has said anything
        self._heap_handoffs: Optional[int] = None   # kubelint: guarded-by(_lock)
        self._heap_frozen = 0       # kubelint: guarded-by(_lock)
        self._heap_swept = 0        # kubelint: guarded-by(_lock)

    def begin_cycle(self, label: str,
                    queue_depths: Optional[Dict[str, int]] = None,
                    fields: Optional[Dict[str, Any]] = None) -> CycleRecord:
        with self._lock:
            self._seq += 1
            seq = self._seq
        return CycleRecord(seq, label, queue_depths=queue_depths,
                           fields=fields,
                           max_spans=self.max_spans_per_cycle)

    def commit_cycle(self, rec: CycleRecord) -> None:
        """Push a finished record into the ring, dropping (and counting)
        the oldest when full."""
        if rec.t1 is None:
            rec.t1 = wallclock()
        with self._lock:
            self._ring.append(rec)
            while len(self._ring) > self.capacity:
                self._ring.popleft()
                self._dropped += 1

    def note_heap(self, handoffs: int, frozen: Optional[int],
                  swept: int = 0) -> None:
        """The heap policy (utils/heap.py) handed the survivors to the
        permanent generation ``handoffs`` times (0: at start-up, which
        only says that a policy serves); frozen: what
        ``gc.get_freeze_count()`` read then, where it was read; swept:
        the unreachable objects a sweep found.  They reach the next
        committed cycle's meta (note_interpreter)."""
        with self._lock:
            self._heap_handoffs = (self._heap_handoffs or 0) + handoffs
            if frozen is not None:
                self._heap_frozen = frozen
            self._heap_swept += swept

    def note_interpreter(self, rec: CycleRecord
                         ) -> Optional[Dict[threading.Thread, float]]:
        """Who held the interpreter since the cycle before (Trace.finish
        calls this on the serving thread): meta ``thread_cpu_s`` =
        {thread name: CPU seconds} of every live Python thread above 0.1
        ms, pool threads summed under their prefix (_fold_name), with
        ``thread_cpu_window_s``, the wall seconds the two readings are
        apart -- both absent on the first cycle after arming and where
        the platform has no per-thread CPU clock; ``gc_other_s``, the
        collector's pauses on threads with no phase or bind job open, and
        ``gc_collections``, its passes on any thread, where not zero;
        while a heap policy serves (note_heap) ``heap_handoffs``, its
        hand-offs since the cycle before (0 or 1), ``heap_frozen``, the
        permanent generation's size when it was last read, and
        ``heap_sweep_collected``, what a sweep found, where not zero.  A
        thread that ends between two readings takes its last slice with
        it.  Returns the reading it took (None where there is no such
        clock): it is the start of the teardown that follows, too."""
        now = time.perf_counter()
        cpu = _read_thread_cpu()
        with self._lock:
            last, last_t = self._thread_cpu, self._thread_cpu_t
            if cpu is not None:
                self._thread_cpu, self._thread_cpu_t = cpu, now
            other, n = self.gc_other_s, self.gc_collections
            d_other, d_n = other - self._gc_other_seen, n - self._gc_seen
            self._gc_other_seen, self._gc_seen = other, n
            handoffs, frozen = self._heap_handoffs, self._heap_frozen
            swept, self._heap_swept = self._heap_swept, 0
            if handoffs is not None:
                self._heap_handoffs = 0
        if handoffs is not None:
            rec.meta["heap_handoffs"] = handoffs
            rec.meta["heap_frozen"] = frozen
            if swept:
                rec.meta["heap_sweep_collected"] = swept
        if d_other > 0.0:
            rec.meta["gc_other_s"] = round(d_other, 6)
        if d_n:
            rec.meta["gc_collections"] = d_n
        if cpu is None or not last_t:
            return cpu
        rec.meta["thread_cpu_s"] = _cpu_by_name(cpu, last)
        rec.meta["thread_cpu_window_s"] = round(now - last_t, 6)
        return cpu

    def cycles(self) -> List[CycleRecord]:
        with self._lock:
            return list(self._ring)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._dropped = 0

    # -- serialization ------------------------------------------------------

    def to_dict(self) -> Dict[str, Any]:
        """The /debug/flightz document."""
        recs = self.cycles()
        return {"armed": True, "capacity": self.capacity,
                "max_spans_per_cycle": self.max_spans_per_cycle,
                "dropped": self.dropped(),
                "cycles": [r.to_dict() for r in recs]}

    def to_pipeline_doc(self, workload: str = "") -> Dict[str, Any]:
        """The pipeline document: a flat stage/cycle span list (the shape
        tools/traceview.py and tools/kubeaot --prune consume).
        ``span_total`` equals the number of ``ph: "X"`` events in
        ``to_chrome_trace()`` for the same ring content — the two exports
        describe the same spans.  Still-OPEN spans (e.g. an async bind in
        flight on a committed record) are excluded from BOTH exports —
        they would serialize with a bogus zero duration; the full
        ``to_dict()``/flightz dump still shows them."""
        recs = self.cycles()
        t_base = recs[0].t0 if recs else 0.0
        spans = []
        for rec in recs:
            for s in rec.spans() + rec.bind_spans():
                if s.t1 is None:
                    continue
                spans.append({
                    "stage": s.name, "cycle": rec.seq,
                    "thread": s.thread,
                    "span_id": s.span_id, "parent_id": s.parent_id,
                    "start_s": round(s.t0 - t_base, 4),
                    "end_s": round(s.t1 - t_base, 4),
                    **({"args": dict(s.args)} if s.args else {})})
        doc = {"workload": workload,
               "cycles": len(recs),
               "dropped": self.dropped(),
               "span_total": len(spans),
               "device_wait_s": round(sum(
                   s.get("args", {}).get("device_wait_s", 0.0)
                   for s in spans), 3),
               # per-cycle meta (pod_bucket, delta_rows, aot stats):
               # tools/kubeaot --prune reads the bucket-hit set from here
               "cycle_meta": [{"seq": r.seq, "label": r.label,
                               "meta": dict(r.meta)} for r in recs],
               "spans": spans}
        if recs:
            doc["total_s"] = round(max((r.t1 or r.t0) for r in recs)
                                   - t_base, 3)
        # durable-journal digest (utils/journal.py): when the journal is
        # armed alongside the recorder, the pipeline doc carries its
        # status — records, bytes, drops, window span and the linkage
        # hit-rate into THIS ring's live cycle seqs — so traceview can
        # print the "journal:" digest from the committed artifact alone
        from . import journal as _journal
        jr = _journal.journal()
        if jr is not None:
            doc["journal"] = jr.status(
                flight_seqs={r.seq for r in recs})
        return doc

    @staticmethod
    def _component_of(thread: str) -> str:
        if thread.startswith("binder"):
            return "binder"
        if "preempt" in thread:
            return "preemption"
        return "scheduler"

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (Perfetto/chrome://tracing loadable):
        one pid per component (scheduler/binder/preemption), one tid per
        thread, ``ph: "X"`` complete spans with microsecond timestamps,
        ``ph: "C"`` queue-depth counters at each cycle start, ``ph: "i"``
        instants for recompile events, and ``ph: "M"`` metadata naming
        processes and threads.  The number of "X" events equals
        ``to_pipeline_doc()["span_total"]``."""
        recs = self.cycles()
        events: List[Dict[str, Any]] = []
        pids: Dict[str, int] = {}
        tids: Dict[Tuple[int, str], int] = {}

        def pid_of(component: str) -> int:
            if component not in pids:
                pid = len(pids) + 1
                pids[component] = pid
                events.append({"ph": "M", "name": "process_name",
                               "pid": pid, "tid": 0,
                               "args": {"name": f"kubetpu-{component}"}})
            return pids[component]

        def tid_of(pid: int, thread: str) -> int:
            key = (pid, thread)
            if key not in tids:
                tid = sum(1 for (p, _t) in tids if p == pid) + 1
                tids[key] = tid
                events.append({"ph": "M", "name": "thread_name",
                               "pid": pid, "tid": tid,
                               "args": {"name": thread}})
            return tids[key]

        def us(t: float) -> int:
            return int(t * 1e6)

        for rec in recs:
            sched_pid = pid_of("scheduler")
            if rec.queue_depths:
                events.append({"ph": "C", "name": "queue_depth",
                               "pid": sched_pid, "tid": 0,
                               "ts": us(rec.t0),
                               "args": {k: int(v) for k, v
                                        in rec.queue_depths.items()}})
            for s in rec.spans() + rec.bind_spans():
                if s.t1 is None:
                    continue   # open span: excluded like to_pipeline_doc
                comp = self._component_of(s.thread)
                pid = pid_of(comp)
                tid = tid_of(pid, s.thread)
                args = {"cycle": rec.seq, "span_id": s.span_id,
                        "parent_id": s.parent_id}
                args.update(s.args)
                events.append({"ph": "X", "name": s.name, "cat": comp,
                               "pid": pid, "tid": tid,
                               "ts": us(s.t0),
                               "dur": max(us(s.t1) - us(s.t0), 0),
                               "args": args})
            for ev in rec.events():
                comp = self._component_of(ev["thread"])
                pid = pid_of(comp)
                tid = tid_of(pid, ev["thread"])
                events.append({"ph": "i", "name": ev["name"], "cat": comp,
                               "pid": pid, "tid": tid, "s": "t",
                               "ts": us(ev["ts"]),
                               "args": dict(ev["args"])})
        return {"traceEvents": events, "displayTimeUnit": "ms"}


_POOL_THREAD = re.compile(r"^(.+)_\d+$")


def _fold_name(name: str) -> str:
    """``binder_3`` -> ``binder_pool``: the threads of a
    ThreadPoolExecutor are one contender for the interpreter."""
    m = _POOL_THREAD.match(name)
    return f"{m.group(1)}_pool" if m else name


def _read_thread_cpu() -> Optional[Dict[threading.Thread, float]]:
    """The CPU seconds of every live Python thread so far, each from its
    own CPU-time clock (two system calls a thread); None where the
    platform has no such clock."""
    clock_of = getattr(time, "pthread_getcpuclockid", None)
    if clock_of is None:
        return None
    out = {}
    for t in threading.enumerate():
        try:
            out[t] = time.clock_gettime(clock_of(t.ident))
        except (OSError, TypeError, OverflowError):
            pass            # it ended since enumerate() listed it
    return out


def _cpu_by_name(cpu: Dict[threading.Thread, float],
                 last: Dict[threading.Thread, float]) -> Dict[str, float]:
    """{thread name: CPU seconds} between two readings, for the threads
    above 0.1 ms, pool threads summed under their prefix."""
    by_name: Dict[str, float] = {}
    for t, c in cpu.items():
        # a thread new since the last reading spent all it has since
        d = c - last.get(t, 0.0)
        if d > 1e-4:
            name = _fold_name(t.name)
            by_name[name] = by_name.get(name, 0.0) + d
    return {k: round(v, 6) for k, v in by_name.items()}


# ------------------------------------------------------- the collector hook
#
# While the recorder is armed ONE function sits in gc.callbacks.  It adds
# every collection's pause (and, for generation 2, one count) to the
# running sums of the thread the collection ran on; a phase or a bind job
# reads them as it opens and as it closes, and what its thread gathered
# in between becomes its args ``gc_s`` / ``gc_full``.  Collections run
# under the interpreter lock and never overlap, so a slot a thread is
# enough and the hook takes no lock.  It must not: a collection can start
# between any two bytecodes, also inside a CycleRecord's lock, so a full
# collection's ``gc`` event is only noted here and recorded when the
# phase or the job closes (_record_gc_events).


class _GcSums:
    __slots__ = ("seconds", "full", "t0", "events")

    def __init__(self):
        self.seconds, self.full, self.t0 = 0.0, 0, 0.0
        self.events: List[Tuple[float, float, int]] = []


def _gc_sums() -> _GcSums:
    g = getattr(_tls, "gc", None)
    if g is None:
        g = _tls.gc = _GcSums()
    return g


def _on_gc(phase: str, info: Dict[str, int]) -> None:
    g = _gc_sums()
    if phase == "start":
        g.t0 = time.perf_counter()
        return
    pause = time.perf_counter() - g.t0
    full = info["generation"] == 2
    g.seconds += pause
    g.full += full
    fr = _flight
    if fr is None:
        return
    fr.gc_collections += 1
    if (getattr(_tls, "phase", None) is None
            and getattr(_tls, "job", None) is None):
        fr.gc_other_s += pause
    elif full:
        g.events.append((wallclock(), pause, info["collected"]))


def _gc_args(g: _GcSums, s0: float, full0: int,
             args: Dict[str, Any]) -> None:
    """What the thread's collector sums gained since (s0, full0), as
    ``gc_s`` and ``gc_full`` (absent when zero)."""
    if g.seconds > s0:
        args["gc_s"] = round(g.seconds - s0, 6)
        if g.full > full0:
            args["gc_full"] = g.full - full0


def _take_gc_events(g: _GcSums) -> List[Tuple[float, float, int]]:
    """The full collections this thread noted since they were last
    taken."""
    evs, g.events = g.events, []
    return evs


def _record_gc_events(rec: CycleRecord, parent_id: int,
                      evs: Sequence[Tuple[float, float, int]]) -> None:
    """Noted full collections as ``gc`` events of ``rec``."""
    for ts, pause, collected in evs:
        rec.event("gc", parent_id=parent_id, ts=ts, generation=2,
                  seconds=round(pause, 6), collected=collected)


# module arming state.  The reference is read WITHOUT a lock on the hot
# path (Trace.__init__): rebinding a Python reference is atomic, a racing
# reader sees either the old or the new recorder, and the disarmed fast
# path must not pay a lock acquisition per cycle.  arm/disarm themselves
# serialize through _flight_lock.
_flight: Optional[FlightRecorder] = None
_flight_lock = threading.Lock()


def flight_recorder() -> Optional[FlightRecorder]:
    """The armed recorder, or None (disarmed, the default)."""
    return _flight


def arm_flight_recorder(capacity: Optional[int] = None,
                        max_spans_per_cycle: Optional[int] = None
                        ) -> FlightRecorder:
    """Idempotently arm the flight recorder (returns the existing one if
    already armed) and hook the collector (_on_gc), once."""
    global _flight
    with _flight_lock:
        if _flight is None:
            _flight = FlightRecorder(
                capacity=capacity,
                max_spans_per_cycle=max_spans_per_cycle)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
        return _flight


def disarm_flight_recorder() -> None:
    global _flight
    with _flight_lock:
        _flight = None
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)


def maybe_arm_from_env() -> Optional[FlightRecorder]:
    """kubetpu/__init__ hook: arms the recorder iff KUBETPU_FLIGHT=1.
    Importing this module never imports jax."""
    if os.environ.get(FLIGHT_ENV, "0") not in ("", "0", "false", "False"):
        return arm_flight_recorder()
    return None


@contextlib.contextmanager
def flight_span(name: str, **args):
    """Span attached to the CURRENT thread's innermost open cycle span
    (used by code — e.g. the preemption wave's what-if readback — that
    has no handle on the cycle's Trace).  No-op when nothing is open."""
    stack = _span_stack()
    if not stack:
        yield None
        return
    rec, parent = stack[-1]
    with rec.span(name, parent_id=parent.span_id if parent else 0,
                  **args) as sp:
        yield sp


def note_instant(name: str, **args) -> None:
    """Record an instant event on the cycle currently open on this
    thread — the hook code with no handle on the cycle's Trace uses
    (sanitize watchdog recompiles, chaos-harness fault injections,
    backend demotions).  Disarmed or outside a cycle this is a no-op."""
    if _flight is None:
        return
    stack = _span_stack()
    if not stack:
        return
    rec, parent = stack[-1]
    rec.event(name, parent_id=parent.span_id if parent else 0, **args)


def note_compile_event(program: str, shapes: str, **what) -> None:
    """Sanitize-watchdog hook: record an XLA compile or cache load as the
    instant event ``xla-compile`` on the cycle currently open on this
    thread (it happens under the phase that called the program).  what:
    ``kind``, ``seconds``, ``t``, ``differs`` (utils/sanitize.py).
    Disarmed or outside a cycle this is a no-op."""
    note_instant("xla-compile", program=program, shapes=shapes[:512],
                 **what)


# -------------------------------------------------------------------- phases
#
# The serving thread's cycle is ONE flat partition into phases -- pop,
# snapshot, prefilter, tensorize, host-masks, dispatch, packed-readback,
# commit -- each a child span of the cycle's root with its wall extent
# and ``cpu_s`` (the thread's CPU seconds over it: wall - cpu is time the
# thread was blocked on the GIL, a lock or the device), and, while a
# profiler capture is active, ONE "Scheduling:<phase>" TraceAnnotation of
# exactly that extent.  Opening a phase closes whichever phase this
# thread still has open, so two are never open at once (the trace
# reduction sums idle time per annotation name; nesting would count a
# gap twice).


class _Phase:
    """An open phase.  As a context manager it yields the FlightSpan (or
    None: recorder disarmed, or past the span cap) so the caller can
    attach args before the exit closes it."""

    __slots__ = ("name", "rec", "span", "ann", "t0", "t1", "cpu0", "cpu_s",
                 "args", "closed", "gc0_s", "gc0_full", "gc_events", "td")

    def __init__(self, name: str, ann: str, rec: Optional[CycleRecord],
                 parent_id: int, args: Dict[str, Any]):
        self.name, self.rec, self.args = name, rec, args
        self.span = self.ann = self.cpu0 = self.td = None
        self.t0 = self.t1 = self.cpu_s = 0.0
        self.closed = False
        self.gc_events: Sequence[Tuple[float, float, int]] = ()
        if _flight is not None or rec is not None:
            g = _gc_sums()
            self.gc0_s, self.gc0_full = g.seconds, g.full
            self.cpu0 = time.thread_time()
            self.t0 = wallclock()
        if rec is not None:
            self.span = rec.begin_span(name, parent_id=parent_id,
                                       t0=self.t0, **args)
            # nested spans and instants (a compile under tensorize, a
            # fence under dispatch) parent under the open phase
            _span_stack().append((rec, self.span))
        if _PROFILE_ACTIVE:
            import jax
            self.ann = jax.profiler.TraceAnnotation(f"{CYCLE_TRACE}:{ann}")
            self.ann.__enter__()

    def __enter__(self):
        return self.span

    def __exit__(self, *exc):
        self.close()
        return False

    def close(self) -> None:
        if self.closed:
            return
        self.closed = True
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        g = None
        if self.cpu0 is not None:
            self.cpu_s = time.thread_time() - self.cpu0
            self.t1 = wallclock()
            if getattr(_tls, "phase", None) is self:    # on its own thread
                # a pop's args reach its span later (Trace.__init__)
                g = _gc_sums()
                _gc_args(g, self.gc0_s, self.gc0_full,
                         self.args if self.span is None else self.span.args)
        if self.rec is not None:
            try:
                _span_stack().remove((self.rec, self.span))
            except ValueError:
                pass        # closed from another thread: its stack, not ours
            if self.span is not None:
                self.span.args["cpu_s"] = round(self.cpu_s, 6)
                self.span.t1 = self.t1
        if g is not None and g.events:
            # a pop closes before its cycle's record exists: the Trace
            # that takes it in records them
            self.gc_events = _take_gc_events(g)
            if self.rec is not None:
                _record_gc_events(self.rec, self.span.span_id
                                  if self.span is not None else 0,
                                  self.gc_events)
        if getattr(_tls, "phase", None) is self:
            _tls.phase = None


def _open_phase(name: str, ann: str, rec: Optional[CycleRecord],
                parent_id: int, args: Dict[str, Any]) -> _Phase:
    """Close the phase this thread has open, open the next."""
    cur = getattr(_tls, "phase", None)
    if cur is not None:
        cur.close()
    _tls.phase = ph = _Phase(name, ann, rec, parent_id, args)
    return ph


def begin_pop():
    """The ``pop`` phase -- the previous cycle's teardown (its outcomes
    and per-pod states freed), the serving loop, the queue pop, the
    per-pod skip check, grouping by profile -- which runs BEFORE the
    cycle's Trace exists.  ``Trace.finish()`` opens it as it closes a
    cycle's last phase, so the partition has no hole between two cycles;
    here the caller picks that one up, which ends its teardown (see
    "teardown" below; ``teardown_s``: how long ago the phase opened) or,
    on a thread that has just started, opens one.  Returns None when
    neither the recorder nor a capture is on (no clock read, no
    allocation); otherwise the caller hands the phase to the ``Trace`` of
    the cycle the pop fed (``pop=``), which closes and records it."""
    if _flight is None and not _PROFILE_ACTIVE:
        return None
    cur = getattr(_tls, "phase", None)
    if cur is not None and cur.name == "pop" and cur.rec is None:
        if cur.td is not None and cur.td.args is None:
            _end_teardown(cur, cur.td)
        return cur
    return _open_phase("pop", "pop", None, 0, {})


# ------------------------------------------------------------------ teardown
#
# A ``pop`` phase that Trace.finish() opened begins with the TEARDOWN of
# the cycle before: the frames' unwinding and the frees of what the cycle
# held, the serving loop dropping the outcomes, the heap policy's boundary
# -- on the serving thread, with the binder lane's job and every other
# Python thread running beside it.  It ends at begin_pop()'s pick-up.  It
# is a span ``teardown`` under ``pop`` (extent = ``pop.teardown_s``) with
# the serving thread's ``cpu_s``, the collector's ``gc_s`` / ``gc_full``
# and ``thread_cpu_s`` = {thread name: CPU seconds inside it} from a
# second reading of every thread's CPU clock (the first is the one
# Trace.finish() took for the cycle's meta; ``read_s``: what the second
# took, wall), and two children the serving thread stamps as it passes
# them: ``teardown-release`` (to the loop's drop of the outcomes) and
# ``heap-boundary``.  In a capture the phase's annotation is
# "Scheduling:teardown" up to the pick-up and "Scheduling:pop" from it:
# one after the other, no hole, never nested.  A pop closes before its
# cycle's record exists, so all three wait on the open phase for the Trace
# that records the pop.
TEARDOWN_SPAN = "teardown"
RELEASE_SPAN = "teardown-release"
HEAP_SPAN = "heap-boundary"


class _Teardown:
    __slots__ = ("threads0", "kids", "t1", "args")

    def __init__(self, threads0: Optional[Dict[threading.Thread, float]]):
        self.threads0 = threads0    # every thread's CPU clock as it began
        self.kids: List[Tuple[str, float, float, Dict[str, Any]]] = []
        self.t1 = 0.0
        self.args: Optional[Dict[str, Any]] = None      # None: still open


def _end_teardown(ph: _Phase, td: _Teardown) -> None:
    td.args = args = {}
    timed = ph.cpu0 is not None     # a capture alone: annotations, no stamps
    if timed:
        cpu_s = time.thread_time() - ph.cpu0
        td.t1 = wallclock()
    if ph.ann is not None:
        ph.ann.__exit__(None, None, None)
        ph.ann = None
    if _PROFILE_ACTIVE:
        import jax
        ph.ann = jax.profiler.TraceAnnotation(f"{CYCLE_TRACE}:{ph.name}")
        ph.ann.__enter__()
    if not timed:
        return
    ph.args["teardown_s"] = round(td.t1 - ph.t0, 6)
    args["cpu_s"] = round(cpu_s, 6)
    _gc_args(_gc_sums(), ph.gc0_s, ph.gc0_full, args)
    if td.threads0 is not None:
        t_read = time.perf_counter()
        threads1 = _read_thread_cpu()
        if threads1 is not None:
            args["thread_cpu_s"] = _cpu_by_name(threads1, td.threads0)
            args["read_s"] = round(time.perf_counter() - t_read, 6)


def _stamped_teardown() -> Optional[_Phase]:
    """The phase whose teardown is open on this thread and stamped (the
    recorder was armed as it opened), else None: no clock read."""
    cur = getattr(_tls, "phase", None)
    if (cur is None or cur.td is None or cur.td.args is not None
            or cur.cpu0 is None):
        return None
    return cur


def _child(ph: _Phase, name: str, since: Tuple[float, float, float],
           args: Dict[str, Any]) -> None:
    t0, cpu0, gc0 = since
    args["cpu_s"] = round(time.thread_time() - cpu0, 6)
    t1 = wallclock()
    gc_s = _gc_sums().seconds - gc0
    if gc_s > 0.0:
        args["gc_s"] = round(gc_s, 6)
    ph.td.kids.append((name, t0, t1, args))


def teardown_mark() -> Optional[Tuple[float, float, float]]:
    """(wallclock(), this thread's CPU seconds, its collector seconds)
    while a stamped teardown is open on this thread, for a child that
    starts now (teardown_child); None otherwise, with no clock read."""
    if _stamped_teardown() is None:
        return None
    return wallclock(), time.thread_time(), _gc_sums().seconds


def teardown_child(name: str, since: Tuple[float, float, float],
                   **args) -> None:
    """A finished child of the open teardown, from ``since`` (a
    teardown_mark() of this thread) to now, with ``cpu_s`` and the
    collector's pauses inside it (``gc_s``)."""
    cur = _stamped_teardown()
    if cur is not None:
        _child(cur, name, since, args)


def teardown_released(outcomes: int) -> None:
    """The serving loop has dropped the cycle's return value: the
    ``teardown-release`` child, from the phase's opening to now (disarmed,
    or with no cycle behind it: nothing, and no clock read)."""
    cur = _stamped_teardown()
    if cur is not None:
        _child(cur, RELEASE_SPAN, (cur.t0, cur.cpu0, cur.gc0_s),
               {"outcomes": outcomes})


# ------------------------------------------------------------------ bind job
#
# The binder lane works off the serving thread's partition, under the
# next cycle's phases, so its job is no phase: ONE finished span
# ``bind-job`` on the record of the cycle whose binds it applies, written
# by the thread that ran it when it is done (the record is in the ring by
# then, as it is when the bind table's stamps land).  It opens no
# TraceAnnotation: the capture's readers take every CYCLE_TRACE name as the
# serving thread's partition, and the span is on the profiler's clock
# through CLOCK_ANNOTATION.
JOB_SPAN = "bind-job"


class JobSpan:
    """A bind job being run, armed (Scheduler._run_bind_job): its wall
    extent, the running thread's CPU seconds over it (wall - cpu is time
    the thread was blocked on the interpreter, a lock or a wake-up) and
    the collector's pauses on that thread."""

    __slots__ = ("rec", "parent_id", "t0", "cpu0", "gc0_s", "gc0_full",
                 "args", "t_settle")

    def __init__(self, rec: CycleRecord, parent: Optional[FlightSpan],
                 handed_t: float = 0.0):
        self.rec = rec
        self.parent_id = parent.span_id if parent is not None else 0
        _tls.job = self
        g = _gc_sums()
        self.gc0_s, self.gc0_full = g.seconds, g.full
        self.t_settle = 0.0
        self.cpu0 = time.thread_time()
        self.t0 = wallclock()
        # handed_t: BindLane.submit's stamp as it queued the job; 0.0 for
        # a job its own hand-over runs (close() raced the lane)
        self.args = ({"wake_s": round(self.t0 - handed_t, 6)}
                     if handed_t else {})

    def settling(self) -> None:
        """The binds are done; what follows is the fold's settling."""
        self.t_settle = wallclock()

    def close(self, **args) -> None:
        t1 = wallclock()
        a = self.args
        a["cpu_s"] = round(time.thread_time() - self.cpu0, 6)
        if self.t_settle:
            a["settle_s"] = round(t1 - self.t_settle, 6)
        a.update(args)
        g = _gc_sums()
        _gc_args(g, self.gc0_s, self.gc0_full, a)
        _tls.job = None
        span = self.rec.record_span(JOB_SPAN, self.t0, t1,
                                    parent_id=self.parent_id, **a)
        _record_gc_events(self.rec, span.span_id if span is not None else 0,
                          _take_gc_events(g))


# --------------------------------------------------------------------- Trace


class Trace:
    """The per-cycle step trace (reference: utiltrace.Trace) — now also
    the flight recorder's cycle handle: when the recorder is armed at
    construction, the Trace owns a CycleRecord, carries a span id, parent
    linkage and thread tag, every ``step()`` interval becomes a child
    span under its upstream message, and every ``phase()`` a child span
    of the cycle's partition.  Disarmed, nothing beyond the original step
    list is touched."""

    def __init__(self, name: str, parent: Optional["Trace"] = None,
                 queue_depths: Optional[Dict[str, int]] = None,
                 pop: Optional[_Phase] = None, **fields):
        self.name = name
        self.fields = fields
        self.start = wallclock()
        self.steps: List[Tuple[float, str]] = []
        self.thread = threading.current_thread().name
        # flight recorder linkage (no lock taken when disarmed: _flight is
        # read once; None short-circuits everything below)
        fr = _flight
        self._fr = fr
        if pop is not None:
            pop.close()
        self.rec: Optional[CycleRecord] = None
        self._root: Optional[FlightSpan] = None
        self.span_id = 0
        self.parent_id = parent.span_id if parent is not None else 0
        if fr is not None:
            self.rec = fr.begin_cycle(name, queue_depths=queue_depths,
                                      fields=dict(fields))
            self._root = self.rec.begin_span(name,
                                             parent_id=self.parent_id)
            if self._root is not None:
                self.span_id = self._root.span_id
            if pop is not None and pop.cpu0 is not None:  # armed when opened
                # the pop that fed this cycle, stamped before the record
                # existed
                sp = self.rec.record_span(
                    "pop", pop.t0, pop.t1, parent_id=self.span_id,
                    cpu_s=round(pop.cpu_s, 6), **pop.args)
                # full passes between two cycles (the heap policy's sweep
                # runs there), noted before the record existed
                _record_gc_events(self.rec,
                                  sp.span_id if sp is not None else 0,
                                  pop.gc_events)
                td = pop.td
                if td is not None and td.args is not None and sp is not None:
                    tsp = self.rec.record_span(
                        TEARDOWN_SPAN, pop.t0, td.t1, parent_id=sp.span_id,
                        **td.args)
                    for name, t0, t1, args in td.kids:
                        self.rec.record_span(
                            name, t0, t1, parent_id=tsp.span_id
                            if tsp is not None else sp.span_id, **args)
        self._last_mark = self.start
        if _PROFILE_ACTIVE:
            _emit_clock(self.rec.seq if self.rec is not None else 0)

    def step(self, msg: str) -> None:
        now = wallclock()
        self.steps.append((now, msg))
        if self.rec is not None:
            # the interval since the previous mark becomes a child span
            self.rec.record_span(msg, self._last_mark, now,
                                 parent_id=self.span_id)
        self._last_mark = now

    def phase(self, name: str, ann: Optional[str] = None, **args):
        """Open the next phase of the cycle's partition (see "phases"
        above), closing the one this thread had open.  Use as a context
        manager, or call it bare and let the next ``phase()`` or
        ``finish()`` close it.  ann: the annotation's
        name where it differs from the span's.  Neither recorder nor
        capture on: the shared no-op, zero allocation, zero locks."""
        if self.rec is None and not _PROFILE_ACTIVE:
            return _NULL_SPAN
        return _open_phase(name, ann or name, self.rec, self.span_id, args)

    @staticmethod
    def note(**args) -> None:
        """Attach args to the span of the phase this thread has open (a
        no-op with the recorder disarmed)."""
        cur = getattr(_tls, "phase", None)
        if cur is not None and cur.span is not None:
            cur.span.args.update(args)

    @staticmethod
    def open_span() -> Optional[FlightSpan]:
        """The span of the phase this thread has open, for a caller that
        counts into its args or hands it to another thread to count
        into (None with the recorder disarmed)."""
        cur = getattr(_tls, "phase", None)
        return cur.span if cur is not None else None

    def stage(self, name: str, **args):
        """Scoped child span INSIDE a phase (preemption wave, decision
        audit...): no annotation.  Returns a no-op context when disarmed
        — zero allocation, zero locks."""
        if self.rec is None:
            return _NULL_SPAN
        return self.rec.span(name, parent_id=self.span_id, **args)

    def finish(self, **meta) -> None:
        """Commit this cycle's record to the recorder's ring (idempotent;
        no-op when disarmed).  meta lands on the record (e.g.
        discarded=True for a pipelined cycle whose dispatch was thrown
        away)."""
        rec, fr = self.rec, self._fr
        self.rec = None
        threads0 = None
        if rec is not None and fr is not None:
            if meta:
                rec.meta.update(meta)
            CycleRecord.end_span(self._root)
            rec.t1 = wallclock()
            threads0 = fr.note_interpreter(rec)
            fr.commit_cycle(rec)
        # the phase still open is this cycle's last (commit, when the
        # cycle ran to its end): closed here, it takes in the hand-over
        # of the record too, and the thread's next pop phase opens at
        # once, with the cycle's teardown (see "teardown" above) -- what
        # follows is on the way to the next pop
        cur = getattr(_tls, "phase", None)
        if cur is not None and cur.name != "pop" \
                and (cur.rec is rec or rec is None):
            cur.close()
            if _flight is not None or _PROFILE_ACTIVE:
                ph = _open_phase("pop", TEARDOWN_SPAN, None, 0, {})
                ph.td = _Teardown(threads0)

    def __del__(self):
        # a cycle that unwound on an exception still commits its
        # record: the crashing cycle is exactly the one the flight
        # recorder exists to capture (CPython refcounting runs this as
        # the serving loop's except-and-continue drops the cycle state)
        try:
            if self.rec is not None:
                self.finish(aborted=True)
        except Exception:
            pass

    def total(self) -> float:
        return wallclock() - self.start

    def log_if_long(self, threshold: float = SLOW_CYCLE_THRESHOLD) -> Optional[str]:
        total = self.total()
        if total < threshold:
            return None
        fields = ",".join(f"{k}:{v}" for k, v in self.fields.items())
        lines = [f'Trace "{self.name}" ({fields}) (total {total * 1000:.0f}ms):']
        last = self.start
        for ts, msg in self.steps:
            lines.append(f"  ---\"{msg}\" {(ts - last) * 1000:.0f}ms")
            last = ts
        out = "\n".join(lines)
        LOG.info(out)
        return out

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.log_if_long()
        self.finish()
        return False
