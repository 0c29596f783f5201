"""The serving process's collector policy: the scheduler decides when its
old heap is walked.

CPython's collector starts a FULL pass whenever the objects promoted
since the last one pass a quarter of the old generation.  A serving
scheduler promotes everything that outlives a cycle -- the store's
events, the snapshot's clones, the resident pods -- into an old
generation that is all live, acyclic data (the warm cache, the mirror,
the programs), so those passes walk a quarter to half a second and free
nothing, every third to eighth cycle, with every Python thread stopped
(PERF.md section 6, PR 38: 55-107 ms a cycle in full passes, `collected`
0 in every one).

Between ``Scheduler.run()`` and ``Scheduler.close()`` this module hands
what survives to the PERMANENT generation (``gc.freeze()``: one list
merge, whatever the heap's size), which no automatic pass traverses:

* at start-up (the end of the blocking prewarm, and again when the
  background ladder ends): ``gc.collect()`` once, then ``gc.freeze()``;
* on the serving thread between two cycles, every HANDOFF_EVERY-th
  cycle: ``gc.collect(1)`` (the young and the middle generation: cyclic
  garbage still dies young, so next to none is pinned) and
  ``gc.freeze()``;
* the safety net, a SWEEP (``gc.unfreeze(); gc.collect();
  gc.freeze()``): a frozen object that dies by reference count is freed
  as always, one that becomes CYCLIC garbage later waits for this.  It
  runs where nobody waits (the queue came back empty) and after a
  recovered cycle (SWEEP_GAP_S apart at the least), and under unbroken
  load every SWEEP_EVERY_S.  A resync asks for none: the two that did,
  on the chip, found nothing and stalled a measured window 1.1 s each
  (PERF.md section 6, PR 39), and SWEEP_EVERY_S bounds what one can pin;
* ``stop()`` (the last serving scheduler's ``close()``) gives the heap
  back: ``gc.unfreeze()``.

The collector stays enabled and its thresholds stay CPython's: the
young passes run as ever, and an automatic full pass, where one still
comes, walks what the last hand-off left.

The permanent generation is the PROCESS's, not a scheduler's:
``unfreeze`` also releases what the embedding process froze itself (a
sweep then walks that too).  Schedulers that serve side by side are
listed here (``_serving``); the last to stop unfreezes.

Armed (the flight recorder, utils/trace.py), every hand-off is counted
into the next cycle's meta: ``heap_handoffs``, ``heap_frozen``,
``heap_sweep_collected`` (FlightRecorder.note_heap).  The passes made
here fire ``gc.callbacks`` like any other, so the recorder's pause
accounting sees them.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import List

from . import trace as utrace

# K: cycles between two hand-offs.  Between two of them everything a
# cycle holds past two young passes (~100,000 objects a 1,024-pod cycle,
# most of them dead by reference count a cycle later) sits in the old
# generation for an automatic full pass to walk; and ``gc.freeze()``
# zeroes the generations' counts, so at K = 1 the old generation is never
# due.  The basic cell read ``gc_pause_ms_per_cycle.sat`` 18.5 / 33.9 /
# 46.4 at K = 1 / 4 / 16 against 111.8 without (PERF.md section 6, PR 39).
HANDOFF_EVERY = 1
# T: under unbroken load, seconds between two sweeps (one pass of <= 0.5 s
# in 300 s: under 0.2% of serving time)
SWEEP_EVERY_S = 300.0
# the least seconds between two sweeps asked for (an empty queue polls
# every 0.2 s; a recovery can repeat)
SWEEP_GAP_S = 60.0
# armed: ``gc.get_freeze_count()`` walks the permanent list (tens of ms
# over a million objects), so ``heap_frozen`` is read this many cycles
# apart at the least, never every cycle
FROZEN_READ_EVERY = 16
# what one ``boundary()`` did (a sweep is a hand-off too)
NOTHING, HANDED_OFF, SWEPT = 0, 1, 2

# process-wide: the policies started and not stopped.  Reentrant: a
# finalizer run by a pass made under the lock may close a scheduler.
_lock = threading.RLock()
_serving: List["HeapPolicy"] = []


class HeapPolicy:
    """One scheduler's part in the policy: ``start()`` from ``run()``,
    ``boundary()`` from the serving thread between two cycles,
    ``stop()`` from ``close()``.  A Scheduler that is never ``run()``
    has none and touches nothing."""

    def __init__(self) -> None:
        self.started = False
        self.handoffs = 0           # cycle-boundary hand-offs made
        self.sweeps = 0
        self.sweep_collected = 0    # unreachable objects the sweeps found
        self._cycle_seen = 0        # cycle count at the last boundary
        self._since_handoff = 0     # cycles since the last hand-off
        self._since_read = 0        # cycles since heap_frozen was read
        self._unswept = False       # a hand-off since the last full pass
        self._sweep_due = False
        self._last_full = 0.0       # time.monotonic() of the last full pass

    # ------------------------------------------------------------ start-up

    def start(self) -> None:
        """The start-up hand-off."""
        with _lock:
            if self.started:
                return
            self.started = True
            _serving.append(self)
        self.startup_handoff()

    def startup_handoff(self) -> None:
        """One full pass, then everything that survived it is permanent
        (the warm cache, the mirror, the programs compiled so far)."""
        with _lock:
            if not self.started:
                return
            gc.collect()
            gc.freeze()
            self._last_full = time.monotonic()
            self._unswept = False
        self._note(0, read=True)

    # ------------------------------------------------------- between cycles

    def boundary(self, cycle_count: int) -> int:
        """The serving thread, with no cycle open.  cycle_count: the
        scheduler's; it has moved when a cycle ran since the last call,
        and stands still when the pop came back empty.  Returns what it
        did: NOTHING, HANDED_OFF or SWEPT (the ``heap-boundary`` span's
        args, utils/trace.py)."""
        ran = cycle_count - self._cycle_seen
        if ran:
            self._cycle_seen = cycle_count
            self._since_handoff += ran
            self._since_read += ran
            if self._since_handoff < HANDOFF_EVERY:
                return NOTHING
        elif not (self._unswept or self._sweep_due):
            return NOTHING      # idle, and nothing frozen since a full pass
        since = time.monotonic() - self._last_full
        if since >= SWEEP_EVERY_S or (since >= SWEEP_GAP_S
                                      and (self._sweep_due or not ran)):
            return self._sweep()
        if ran:
            return self._hand_off()
        return NOTHING

    def want_sweep(self) -> None:
        """A recovered cycle dropped residents (the chain, the profile's
        tensorizer): what they held may have been frozen, and may be
        cyclic."""
        self._sweep_due = True

    def _hand_off(self) -> int:
        with _lock:
            if not self.started:
                return NOTHING
            gc.collect(1)
            gc.freeze()
        self._since_handoff = 0
        self._unswept = True
        self.handoffs += 1
        self._note(1, read=self._since_read >= FROZEN_READ_EVERY)
        return HANDED_OFF

    def _sweep(self) -> int:
        with _lock:
            if not self.started:
                return NOTHING
            gc.unfreeze()
            found = gc.collect()
            gc.freeze()
            self._last_full = time.monotonic()
        self._since_handoff = 0
        self._unswept = self._sweep_due = False
        self.handoffs += 1
        self.sweeps += 1
        self.sweep_collected += found
        self._note(1, read=True, swept=found)
        return SWEPT

    def _note(self, handoffs: int, read: bool, swept: int = 0) -> None:
        """Armed only: tell the flight recorder; read: walk the permanent
        list for ``heap_frozen`` too."""
        fr = utrace.flight_recorder()
        if fr is None:
            return
        frozen = None
        if read:
            self._since_read = 0
            frozen = gc.get_freeze_count()
        fr.note_heap(handoffs, frozen, swept)

    # ---------------------------------------------------------------- close

    def stop(self) -> None:
        """Give the heap back.  While another scheduler still serves, its
        next sweep takes what this one leaves; the last one unfreezes."""
        with _lock:
            if not self.started:
                return
            self.started = False
            _serving.remove(self)
            if _serving:
                for other in _serving:
                    other.want_sweep()
                return
            gc.unfreeze()
