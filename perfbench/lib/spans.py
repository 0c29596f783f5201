"""What the readers of the program's span tree share (PR 26).

The program's flight recorder (kubetpu/utils/trace.py) gives every cycle
one flat partition of the serving thread's time into PHASES, each a span
with its wall extent and ``args.cpu_s``; the commit loop's split as sums
on the ``commit`` span's args; a BIND TABLE (``binds``: one row a pod,
[submitted, started, done, thread] on the program's wallclock());
``xla-compile`` events with ``seconds``; and, in a profiler capture, one
``kubetpu.clock`` host event a cycle whose ``wallclock_s`` stat ties that
clock to the profiler's.

Every function here takes ``ctx`` as ``lib/readers.py`` describes it and
returns a number, or None when the program recorded no such span, arg or
row (a program from before PR 26, a run off the chip for the device
part): it never raises for want of something to read.  Means are over the
in-window cycles, percentiles over all pods of those cycles.
"""

from __future__ import annotations

import os
import statistics
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import drive, stats, xplane
from .readers import AUCTION_PROGRAM

PHASES = ("pop", "snapshot", "prefilter", "tensorize", "host-masks",
          "dispatch", "packed-readback", "commit")
READBACK = "packed-readback"
# the thread name the program's one binder lane stamps on its rows
# (kubetpu/bindlane.py, PR 27); a pooled bind carries ``binder_<n>``
LANE_THREAD = "binder-lane"
CLOCK_EVENT = "kubetpu.clock"
# how far before its own dispatch a device event may appear to lie
SKEW_SLACK_S = 0.005


def named(cycle: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    return [s for s in cycle["spans"] if s["name"] == name]


def _dur(span: Dict[str, Any]) -> float:
    return span["t1"] - span["t0"]


def _arg_sum(spans: Iterable[Dict[str, Any]], *args: str) -> float:
    return sum(float(s["args"].get(a, 0.0)) for s in spans for a in args)


def _mean_ms(per_cycle: List[float]) -> Optional[float]:
    return 1e3 * statistics.fmean(per_cycle) if per_cycle else None


# ---------------------------------------------------------------- phases


def span_ms_per_cycle(ctx, name: str, minus_arg: Optional[str] = None
                      ) -> Optional[float]:
    """Mean ms a cycle in the spans of that name, over the cycles that
    have one; less the seconds in ``args[minus_arg]`` where given."""
    per = []
    for c in ctx.cycles:
        sp = named(c, name)
        if sp:
            less = _arg_sum(sp, minus_arg) if minus_arg else 0.0
            per.append(sum(map(_dur, sp)) - less)
    return _mean_ms(per)


def arg_ms_per_cycle(ctx, name: str, *args: str) -> Optional[float]:
    """Mean ms a cycle of the summed ``args`` (seconds) of the spans of
    that name, over the cycles whose span carries the first of them."""
    per = []
    for c in ctx.cycles:
        sp = [s for s in named(c, name) if args[0] in s["args"]]
        if sp:
            per.append(_arg_sum(sp, *args))
    return _mean_ms(per)


def child_ms_per_cycle(ctx, child: str, witness: str) -> Optional[float]:
    """Mean ms a cycle in the spans named ``child``, over the cycles that
    have a span named ``witness`` -- 0 for a cycle with the witness and
    no child: the step did not run, which is a reading, not a gap."""
    per = [sum(map(_dur, named(c, child))) for c in ctx.cycles
           if named(c, witness)]
    return _mean_ms(per)


def blocked_pct(ctx, name: str) -> Optional[float]:
    """100 x (1 - thread CPU seconds / wall seconds) over the spans of
    that name: the share of the phase the serving thread spent NOT
    running -- waiting for the GIL, a lock or the device."""
    cpu = wall = 0.0
    for c in ctx.cycles:
        for s in named(c, name):
            if "cpu_s" in s["args"]:
                cpu += float(s["args"]["cpu_s"])
                wall += _dur(s)
    return 100.0 * (1.0 - cpu / wall) if wall > 0 else None


# ------------------------------------------------------------ bind table


def _bind_rows(ctx):
    """(cycle, submitted, started, done) of every complete row."""
    for c in ctx.cycles:
        for row in c.get("binds", ()):
            sub, start, done = row[0], row[1], row[2]
            if sub > 0.0 and start > 0.0 and done > 0.0:
                yield c, sub, start, done


def _p95_ms(values: List[float]) -> Optional[float]:
    return 1e3 * stats.percentile(values, 95) if values else None


def bind_queue_wait_p95_ms(ctx) -> Optional[float]:
    """started - submitted, 95th percentile over the pods.

    What it MEANS changed with the program in PR 27, the arithmetic did
    not.  Before, a pod's bind was submitted to a 16-thread pool from
    inside the commit loop and this was its wait in the pool's queue.
    Since PR 27 a row's ``submitted`` is still stamped in the commit
    loop, but the cycle's binds are ONE job handed to one binder lane as
    the loop ends, and ``started`` is stamped when the lane reaches the
    row: after the rest of the commit loop, the hand-over's wait for the
    lane's previous job, and every earlier row of its own job.  So it
    reads the pod's POSITION IN A SERIAL JOB plus the rest of the loop
    (p95 ~ 0.95 x the job's length + what is left of the loop; 765-814
    ms, where the pool read 383-642; my chip runs, PR 27), not
    contention for a pool.  ``lane_busy_ms_per_cycle`` reads the job's
    length itself."""
    return _p95_ms([start - sub for _, sub, start, _ in _bind_rows(ctx)])


def bind_exec_p95_ms(ctx) -> Optional[float]:
    """How long a bind runs: done - started."""
    return _p95_ms([done - start for _, _, start, done in _bind_rows(ctx)])


def bind_done_lag_p95_ms(ctx) -> Optional[float]:
    """How long after its cycle's readback a bind lands: done - the end
    of the cycle's packed-readback span, 95th percentile over the pods.

    Since PR 27 the binds of a cycle run as one serial job AFTER the
    commit loop, under the next cycle's pop / prepare / readback, so this
    is the whole commit loop + the hand-over + the pod's position in the
    job (819-913 ms against 647-890 with the pool; my chip runs, PR 27):
    it is what a pod's bind latency owes to everything after the device
    answered, and it no longer shrinks when a pool thread is free."""
    lags = []
    for c, _, _, done in _bind_rows(ctx):
        rb = named(c, READBACK)
        if rb:
            lags.append(done - max(s["t1"] for s in rb))
    return _p95_ms(lags)


def lane_busy_ms_per_cycle(ctx, thread: str = LANE_THREAD
                           ) -> Optional[float]:
    """Mean ms a cycle between the lane's first ``started`` and its last
    ``done`` over the cycle record's rows whose thread is ``thread``:
    the wall extent of the cycle's one bind job on the binder lane (PR
    27).  It is the lane's busy time a cycle as seen from outside: a
    moment in which the lane thread waits for the interpreter or a lock
    counts as busy.  Held against the cycle's period it says whether the
    lane can be the pace-setter: a hand-over waits until the job before
    it is applied.  Over the cycles that have such a row, complete rows
    only; None where no row names the thread (a program before PR 27)."""
    per = []
    for c in ctx.cycles:
        rows = [(row[1], row[2]) for row in c.get("binds", ())
                if len(row) > 3 and row[3] == thread
                and row[1] > 0.0 and row[2] > 0.0]
        if rows:
            per.append(max(d for _, d in rows) - min(s for s, _ in rows))
    return _mean_ms(per)


# --------------------------------------------------------------- compile


def compile_stall_ms(ctx) -> Optional[float]:
    """Summed ``seconds`` of the xla-compile events of the in-window
    cycles, ms: what ``window_compiles`` cost.  None when the events
    carry no seconds (a program from before PR 26)."""
    evs = [e for c in ctx.cycles for e in c.get("events", ())
           if e["name"] == "xla-compile"]
    if any("seconds" not in e["args"] for e in evs):
        return None
    if not evs and not any(named(c, "pop") for c in ctx.cycles):
        return None      # no event, and no sign the program could say
    return 1e3 * sum(float(e["args"]["seconds"]) for e in evs)


# ------------------------------------------------- the profiler's clock


def clock_events(pd) -> List[Tuple[float, float]]:
    """(start on the profiler's clock, wallclock_s) of every
    ``kubetpu.clock`` host event of a loaded trace."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith(xplane.HOST_PLANE):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name != CLOCK_EVENT:
                    continue
                wall = dict(ev.stats).get("wallclock_s")
                if wall is not None:
                    out.append((ev.start_ns * 1e-9, float(wall)))
    return out


def clock_offset(pd) -> Optional[Dict[str, float]]:
    """profiler time = wallclock() + offset.  ``offset`` is the median of
    (event start - wallclock_s) over the capture's clock events,
    ``spread_s`` the distance between the largest and the smallest: the
    alignment error.  None without such events."""
    offs = [start - wall for start, wall in clock_events(pd)]
    if not offs:
        return None
    return {"offset": statistics.median(offs), "n": len(offs),
            "spread_s": max(offs) - min(offs)}


def device_ends(tree, program: str = AUCTION_PROGRAM
                ) -> List[Tuple[float, float]]:
    """(start of the program's execution, end of the last device
    operation inside it) for every execution of the programs whose name
    contains ``program``, on the first device plane."""
    devs = sorted(n for n in tree if n.startswith(xplane.DEVICE_PREFIX))
    if not devs:
        return []
    lines = tree[devs[0]]
    ops = sorted((s, e) for _, s, e in lines.get(xplane.OPS_LINE, []))
    out = []
    for name, m0, m1 in sorted(lines.get(xplane.MODULES_LINE, []),
                               key=lambda ev: ev[1]):
        if program not in name:
            continue
        inside = [e for s, e in ops if s >= m0 and e <= m1 + 1e-9]
        out.append((m0, max(inside) if inside else m1))
    return out


def readback_wake_ms(cycles: List[Dict[str, Any]], pd) -> Optional[float]:
    """Mean ms between the end of the auction's last device operation and
    the serving thread's return from the readback, per cycle: for every
    in-capture cycle, the end of its packed-readback span (put on the
    profiler's clock through ``clock_offset``) minus the end of the last
    ``XLA Ops`` event of the ``*schedule_gang*`` execution that lies
    between the cycle's dispatch and that moment.

    The device plane's clock is the chip's, and a capture's host and
    device planes can sit a millisecond or two apart (in the recorded
    perfbench/testdata/v5e_clock.xplane.pb the program appears to start
    1.3 ms BEFORE the host enqueued it): the number includes that skew,
    and a match allows for it (``SKEW_SLACK_S``)."""
    clock = clock_offset(pd)
    if clock is None:
        return None
    ends = device_ends(xplane.planes(pd))
    wakes = []
    for c in cycles:
        rb, dp = named(c, READBACK), named(c, "dispatch")
        if not rb or not dp:
            continue
        t_disp = min(s["t0"] for s in dp) + clock["offset"]
        t_back = max(s["t1"] for s in rb) + clock["offset"]
        mine = [end for start, end in ends
                if t_disp - SKEW_SLACK_S <= start < t_back]
        if mine:
            wakes.append(t_back - max(mine))
    return _mean_ms(wakes)


_loaded: Dict[Tuple[str, float], Any] = {}


def capture_of(ctx):
    """The run's own profiler capture, loaded once a process; None where
    there is none to read (a rehearsal off the chip)."""
    try:
        path = xplane.find_trace(os.path.join(ctx.cell.root, drive.SCRATCH,
                                              "trace"))
        key = (path, os.path.getmtime(path))
    except OSError:
        return None
    if key not in _loaded:
        _loaded.clear()
        _loaded[key] = xplane.load(path)
    return _loaded[key]


def readback_wake_ms_per_cycle(ctx) -> Optional[float]:
    if ctx.device.get("platform") != "tpu":
        return None
    pd = capture_of(ctx)
    return readback_wake_ms(ctx.cycles, pd) if pd is not None else None
