"""The benchmark's client: the only thing that talks to the store.

It offers pods (``store.add``), learns of binds only from the Pod watch
(``store.subscribe``), stamping each with ``time.perf_counter()`` as the
watch delivers it, and deletes pods (``store.delete``).  Nothing it
reports is read from inside the scheduler.

Steady population: whenever more than ``resident_bound`` measured pods
are bound, the oldest-bound ones are deleted.  Upstream scheduler_perf
only creates; the departures are what keeps the window inside one
pod-axis bucket of the resident cluster however fast the scheduler is.

The client shares the interpreter with the serving path, so pod objects
are built before the window (``PodPool``) and every run reports how late
the generator ran.

The event log (``log``) is what check (a) replays:
("add", name, t), ("bind", name, node, t), ("delete", name, t), in the
order this client observed them.  A delete is logged BEFORE the store
call: the scheduler cannot use the freed room earlier than that, so the
replay never sees a bind into room it thinks is still taken.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from . import traffic as _traffic

ROLE = "measured"


class PodPool:
    """Measured pods built ahead of need: (record, API object) pairs."""

    def __init__(self, make_record: Callable[[int], Any],
                 make_object: Callable[[Any], Any], size: int):
        self._make_record = make_record
        self._make_object = make_object
        self.records: Dict[str, Any] = {}
        self._ready: collections.deque = collections.deque()
        self._next = 0
        self.built_late = 0       # built after the pool ran dry
        self.fill(size)

    def fill(self, n: int) -> None:
        for _ in range(n):
            rec = self._make_record(self._next)
            self._next += 1
            self.records[rec.name] = rec
            self._ready.append((rec, self._make_object(rec)))

    def take(self):
        if not self._ready:
            self.built_late += 1
            self.fill(1)
        return self._ready.popleft()


class Client:
    def __init__(self, store, traffic: Dict[str, Any], pool: PodPool,
                 clock: Callable[[], float] = time.perf_counter):
        _traffic.validate(traffic)
        if traffic["kind"] != "closed":
            raise NotImplementedError(
                f"traffic kind {traffic['kind']!r} has a generator "
                "(lib/traffic.py) and no driver yet: the open-loop client "
                "comes with the first cell that needs it (PERF.md, Open "
                "questions)")
        self.store = store
        self.traffic = traffic
        self.pool = pool
        self.clock = clock
        self.resident_bound = int(traffic["resident_bound"])
        self.log: List[tuple] = []
        self._bindq: collections.deque = collections.deque()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._objects: Dict[str, Any] = {}     # live pod name -> API object
        self._resident: collections.deque = collections.deque()
        # per offered pod, by name
        self.bound_t: Dict[str, float] = {}    # the watch's bind stamp
        self.order: List[str] = []             # names in offer order
        # closed loop: (bind stamp, replacement offered - bind seen)
        self.replace_late: List[tuple] = []
        self._replace = True
        self._dip = False
        self._surge = 0
        self._surge_binds = 0
        self.error: Optional[BaseException] = None
        store.subscribe("Pod", self._on_pod)

    # -- the watch (runs on the binder's thread) ----------------------------

    def _on_pod(self, event, old, new) -> None:
        if (event == "update" and new.spec.node_name
                and not old.spec.node_name):
            t = self.clock()
            name = new.metadata.name
            self.log.append(("bind", name, new.spec.node_name, t))
            self._bindq.append((name, t))
            self._wake.set()

    # -- actions ------------------------------------------------------------

    def _offer(self) -> float:
        rec, obj = self.pool.take()
        name = rec.name
        t = self.clock()
        self.log.append(("add", name, t))
        self.order.append(name)
        self._objects[name] = obj
        self.store.add(obj)
        return t

    def _delete(self, name: str) -> None:
        obj = self._objects.pop(name)
        self.log.append(("delete", name, self.clock()))
        self.store.delete(obj)

    def _drain_binds(self) -> int:
        n = 0
        while self._bindq:
            name, t = self._bindq.popleft()
            n += 1
            if name not in self._objects:
                continue            # not ours (or bound after its delete)
            self.bound_t.setdefault(name, t)
            self._resident.append(name)
            if self._replace:
                self.replace_late.append((t, self._offer() - t))
            # inside the loop: under load the queue is never empty, and
            # the population has to hold all the same
            if self._surge and len(self._resident) \
                    >= self.resident_bound + self._surge:
                # held for two populations' worth of binds, so that a
                # scheduling cycle is sure to see it
                self._surge_binds += 1
                if self._surge_binds > 2 * self.resident_bound:
                    self._surge = 0
            while len(self._resident) > self.resident_bound + self._surge:
                self._delete(self._resident.popleft())
        if self._dip:
            self._dip = False
            while self._resident:
                self._delete(self._resident.popleft())
        return n

    # -- the thread ---------------------------------------------------------

    def start(self) -> None:
        self._thread = threading.Thread(target=self._guarded, daemon=True,
                                        name="perfbench-client")
        self._thread.start()

    def _guarded(self) -> None:
        try:
            self._run_closed()
        except BaseException as e:     # surfaced by stop(); never swallowed
            self.error = e

    def _run_closed(self) -> None:
        for _ in range(int(self.traffic["depth"])):
            self._offer()
        while not self._stop.is_set():
            self._wake.clear()
            if not self._drain_binds():
                self._wake.wait(0.002)

    # -- control (main thread) ------------------------------------------------

    def dip(self) -> None:
        """Warm-up helper: delete every resident pod at once.  The cycle
        that sees it has twice the usual churn and the next, while the
        population refills, has no departures at all: the delta-row
        buckets on both sides of the steady state's get compiled or
        loaded before the window, without the population ever rising."""
        self._dip = True
        self._wake.set()

    def surge(self, extra: int) -> None:
        """Warm-up helper: let the population rise ``extra`` above
        ``resident_bound``, hold it there for two populations' worth of
        binds, then fall back.  The resident pod axis
        only ever grows, and the steady population can sit just under a
        bucket edge that one busy cycle crosses: this crosses it before
        the window, where the programs of the next bucket are set-up."""
        self._surge = int(extra)

    def stop_offering(self) -> None:
        self._replace = False

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError("perfbench client thread did not stop")
        if self.error is not None:
            raise self.error

    # -- what the run reports -------------------------------------------------

    def bound_count(self) -> int:
        return len(self.bound_t)

    def pending_count(self) -> int:
        return len(self.order) - len(self.bound_t)
