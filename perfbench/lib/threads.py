"""What the readers of the program's interpreter accounting share (PR 38).

Since PR 38 the flight recorder (kubetpu/utils/trace.py) says who held
the one interpreter.  The binder lane writes ONE span ``bind-job`` a job
on its cycle's record, with ``args.cpu_s`` (the lane thread's own CPU
seconds over it), which ``spans.blocked_pct`` and
``spans.arg_ms_per_cycle`` read as they read a phase.  Every cycle's
``meta`` carries ``thread_cpu_s`` = {thread name: CPU seconds since the
cycle before finished} for every live Python thread, pool threads under
``<prefix>_pool``, beside ``thread_cpu_window_s``, the wall seconds those
readings are apart (the cycle's period).  The collector's pauses ride the
spans of the threads they fell on: ``args.gc_s`` / ``args.gc_full`` on a
phase or on ``bind-job``, absent when zero; ``meta.gc_other_s`` for the
threads that have neither open (the client, a pool thread), and
``meta.gc_collections``, the passes of any generation on any thread.

Every function takes ``ctx`` as ``lib/readers.py`` describes it and
returns a number, or None for a program that does not say (any before PR
38, or a platform without a per-thread CPU clock): it never raises for
want of something to read.  Means are over the in-window cycles that say.
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, Iterable, List, Optional

from . import spans

JOB_SPAN = "bind-job"
CPU_META, WINDOW_META = "thread_cpu_s", "thread_cpu_window_s"
# the spans a collector pause is charged to: the thread's open phase, or
# the lane's job
GC_SPANS = spans.PHASES + (JOB_SPAN,)


def _mean(per_cycle: List[float], scale: float = 1.0) -> Optional[float]:
    return scale * statistics.fmean(per_cycle) if per_cycle else None


def serving_thread(cycle: Dict[str, Any]) -> Optional[str]:
    """The name of the thread that ran the cycle: its root span's."""
    return next((s["thread"] for s in cycle["spans"] if s["parent"] == 0),
                None)


def thread_cpu_ms_per_cycle(ctx, others_only: bool = False
                            ) -> Optional[float]:
    """Mean ms a cycle of ``meta.thread_cpu_s`` summed over the threads;
    others_only: without the cycle's serving thread and the binder
    lane."""
    per = []
    for c in ctx.cycles:
        cpu = c["meta"].get(CPU_META)
        if cpu is None:
            continue
        skip = ({serving_thread(c), spans.LANE_THREAD} if others_only
                else ())
        per.append(sum(v for k, v in cpu.items() if k not in skip))
    return _mean(per, 1e3)


def thread_cpu_ms_by_name(cycles: Iterable[Dict[str, Any]]
                          ) -> Dict[str, float]:
    """Mean ms a cycle by thread name, over the cycles that say, with the
    mean window under ``(window)``: for a report, no metric reads it."""
    said = [c["meta"] for c in cycles if CPU_META in c["meta"]]
    if not said:
        return {}
    names = sorted({k for m in said for k in m[CPU_META]})
    out = {k: 1e3 * sum(m[CPU_META].get(k, 0.0) for m in said) / len(said)
           for k in names}
    out["(window)"] = 1e3 * statistics.fmean(m[WINDOW_META] for m in said)
    return out


def _says_gc(cycles: List[Dict[str, Any]]) -> bool:
    """Did the program hook the collector: some cycle counts a collection
    or carries a pause.  (A pause is absent where it is zero, so a cycle
    alone cannot tell.)"""
    return any("gc_collections" in c["meta"] or "gc_other_s" in c["meta"]
               or any("gc_s" in s["args"] for s in c["spans"])
               for c in cycles)


def _gc_sum(cycle: Dict[str, Any], arg: str) -> float:
    return sum(s["args"].get(arg, 0) for s in cycle["spans"]
               if s["name"] in GC_SPANS)


def gc_pause_ms_per_cycle(ctx) -> Optional[float]:
    """Mean ms a cycle the collector held the interpreter: ``gc_s`` over
    the cycle's phases and its bind job, plus ``meta.gc_other_s``."""
    if not _says_gc(ctx.cycles):
        return None
    return _mean([_gc_sum(c, "gc_s") + c["meta"].get("gc_other_s", 0.0)
                  for c in ctx.cycles], 1e3)


def gc_full_collections_per_cycle(ctx) -> Optional[float]:
    """Mean generation-2 collections a cycle on the serving thread and the
    lane (``gc_full``); one on another thread shows in ``gc_other_s``
    alone."""
    if not _says_gc(ctx.cycles):
        return None
    return _mean([float(_gc_sum(c, "gc_full")) for c in ctx.cycles])
