"""What the readers of the teardown inside ``pop`` share (PR 51).

Since PR 51 a cycle's ``pop`` span has a child ``teardown`` (the phase's
opening, as the cycle before finished, to ``begin_pop()``'s pick-up: the
extent ``pop.teardown_s`` always was) with ``args.cpu_s`` (the serving
thread's own CPU seconds over it), ``args.thread_cpu_s`` = {thread name:
CPU seconds INSIDE the teardown} for every live Python thread above 0.1
ms, pool threads under ``<prefix>_pool`` (``meta.thread_cpu_s`` is the
same reading over a whole period), and two children, ``teardown-release``
and ``heap-boundary``.  ``pop`` itself says ``queue_s`` (``pop_batch``,
its wait ``wait_s`` included) and ``group_s`` (the skip check a pod and
the grouping).

Every function takes ``ctx`` as ``lib/readers.py`` describes it and
returns a number, or None for a program that does not say (any before PR
51, a platform without a per-thread CPU clock, a caller that kept the
outcomes): it never raises for want of something to read.  Means are over
the in-window cycles that say.
"""

from __future__ import annotations

import statistics
from typing import Any, Callable, Dict, Optional

from . import spans, threads

SPAN, RELEASE_SPAN, HEAP_SPAN = "teardown", "teardown-release", \
    "heap-boundary"
CPU_ARG = "thread_cpu_s"


def thread_cpu_ms_per_cycle(ctx, keep: Callable[[Dict[str, Any], str], bool]
                            ) -> Optional[float]:
    """Mean ms a cycle of ``teardown.thread_cpu_s`` over the thread names
    ``keep(cycle, name)`` takes; a cycle whose teardown names none of them
    reads 0: they did not run, which is a reading, not a gap."""
    per = []
    for c in ctx.cycles:
        said = [s["args"][CPU_ARG] for s in spans.named(c, SPAN)
                if CPU_ARG in s["args"]]
        if said:
            per.append(sum(v for cpu in said for k, v in cpu.items()
                           if keep(c, k)))
    return 1e3 * statistics.fmean(per) if per else None


def lane_cpu_ms_per_cycle(ctx) -> Optional[float]:
    return thread_cpu_ms_per_cycle(
        ctx, lambda c, name: name == spans.LANE_THREAD)


def other_threads_cpu_ms_per_cycle(ctx) -> Optional[float]:
    """Without the cycle's serving thread and the binder lane: the
    client, the HTTP server, the periodic loops."""
    return thread_cpu_ms_per_cycle(
        ctx, lambda c, name: name not in (threads.serving_thread(c),
                                          spans.LANE_THREAD))


def pop_queue_ms_per_cycle(ctx) -> Optional[float]:
    """``pop.queue_s - pop.wait_s``: the queue's own work in
    ``pop_batch``, its blocked wait taken out."""
    per = [float(s["args"]["queue_s"]) - float(s["args"].get("wait_s", 0.0))
           for c in ctx.cycles for s in spans.named(c, "pop")
           if "queue_s" in s["args"]]
    return 1e3 * statistics.fmean(per) if per else None
