"""The binder lane: ONE long-lived thread that applies bind jobs in the
order they were handed over.

The serving thread's commit loop assumes a cycle's pods one by one and
hands all of their binds over as ONE job (Scheduler._commit_group);
upstream starts a goroutine a pod (scheduler.go:628), which under one
interpreter lock is a lock convoy: sixteen binder threads against the
serving thread on the cache's, the queue's, the store's and the
histograms' locks.  One lane leaves each of those locks two contenders,
keeps the binds of a cycle in batch order and the jobs of two cycles in
cycle order.  It holds ONE job at a time: a hand-over waits until the job
before it is applied, so the pods that are assumed and not yet bound stay
within a cycle's worth however fast the serving thread runs (the pool
held the serving thread back by taking the interpreter from it; the lane
has to say so).  A bind that would BLOCK (a Permit wait, an HTTP bind, a
retry ladder's sleep) never rides the lane: the scheduler sends it to its
thread pool, where blocking costs the other binds nothing.
"""

from __future__ import annotations

import logging
import queue
import threading
from typing import Callable, List, Optional

from .utils import trace as utrace


class BindJob:
    """One hand-over: the binds of one cycle (or of one singly committed
    pod) that ride the lane, plus the futures of those that were sent to
    the pool instead.  Duck-types the part of ``Future`` that
    ``Scheduler.wait_for_inflight_binds`` uses."""

    __slots__ = ("entries", "flight", "span", "handed_t", "lane_ok",
                 "pooled", "error", "_applied")

    def __init__(self, flight=None, lane_ok: bool = True):
        # (fwk, qp, state, assumed, node_name, row) a pod, batch order
        self.entries: List[tuple] = []
        # the cycle's CycleRecord (bind table) and the commit phase's span
        # (its ``binds_pooled`` arg), both None disarmed
        self.flight = flight
        self.span = None
        # armed: wallclock() as the lane's queue took the job (``wake_s``
        # of its ``bind-job`` span counts from here)
        self.handed_t = 0.0
        # False: nothing of this hand-over may ride the lane (a remote
        # Bind client, an armed chaos bind fault)
        self.lane_ok = lane_ok
        # futures of this hand-over's pooled binds: appended by the
        # serving thread before the hand-over, by the lane during it
        self.pooled: List = []
        self.error: Optional[BaseException] = None
        self._applied = threading.Event()

    def applied(self) -> None:
        self._applied.set()

    def done(self) -> bool:
        return self._applied.is_set() and all(f.done() for f in self.pooled)

    def result(self, timeout: Optional[float] = None) -> None:
        """Wait until every bind of the hand-over has run; raises
        TimeoutError past ``timeout`` and the first exception a bind
        raised, as a pool future's ``result`` does."""
        if not self._applied.wait(timeout):
            raise TimeoutError()
        for f in list(self.pooled):
            f.result(timeout)
        if self.error is not None:
            raise self.error


class BindLane:
    """A thread and a queue of depth one.  The thread starts with the
    first job, so a scheduler that binds synchronously never has one."""

    def __init__(self, run_job: Callable[[BindJob], None],
                 name: str = "binder-lane"):
        self._run_job = run_job
        self._name = name
        self._jobs: "queue.SimpleQueue[Optional[BindJob]]" = \
            queue.SimpleQueue()
        # taken by a hand-over, given back when its job is applied: the
        # next hand-over waits for it
        self._room = threading.Semaphore(1)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None  # kubelint: guarded-by(_lock)

    def submit(self, job: BindJob) -> bool:
        """Hand ``job`` over once the job before it is applied (waits for
        that, off the interpreter lock).  False once the lane is closed:
        the caller applies the job itself, still after the one before."""
        self._room.acquire()
        with self._lock:
            if self._stop.is_set():
                self._room.release()
                return False
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name=self._name)
                self._thread.start()
            if job.flight is not None:
                job.handed_t = utrace.wallclock()
            self._jobs.put(job)
        return True

    def _loop(self) -> None:
        while True:
            job = self._jobs.get()
            if job is None:         # close(): everything before it is done
                return
            try:
                self._run_job(job)
            except Exception as e:   # noqa: BLE001 — the lane outlives a job
                logging.getLogger("kubetpu").exception("bind job failed")
                job.error = job.error or e
            finally:
                job.applied()
                self._room.release()

    def close(self, timeout: float = 10.0) -> None:
        """Idempotent.  Jobs already queued are still applied, in order;
        the wait for them is bounded."""
        with self._lock:
            if not self._stop.is_set():
                self._stop.set()
                self._jobs.put(None)
            t = self._thread
        if (t is not None and t is not threading.current_thread()
                and t.is_alive()):
            t.join(timeout)


class BindFold:
    """What a run of binds owes the cache (FinishBinding) and the
    histograms, whose locks it would otherwise take once a pod.  Filled by
    ``Scheduler._bind_cycle_inner``, settled by
    ``Scheduler._settle_bind_fold`` -- once a job on the lane, once a pod
    anywhere else -- with the same counts and sums either way."""

    __slots__ = ("job", "points", "bind_s", "scheduled", "finished")

    def __init__(self, job: Optional[BindJob] = None):
        self.job = job               # the lane's job; None off the lane
        self.points: List[tuple] = []      # (seconds, point, status)
        self.bind_s: List[tuple] = []      # (seconds,)
        self.scheduled: List[tuple] = []   # (attempts, since first, e2e)
        self.finished: List = []           # assumed pods whose bind landed
