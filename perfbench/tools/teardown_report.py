#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, plus what
the result line does not say about the inside of ``pop`` (PR 51):

    python3 perfbench/tools/teardown_report.py --workload <cell> --seed <n> --seconds <s>

For the in-window cycles, means in ms a cycle: the ``pop`` span less its
wait, its ``teardown`` child against ``pop.teardown_s`` (one extent, two
names), the teardown's make-up -- the serving thread's own CPU, every
other thread's CPU inside it by NAME, and what is left, in which no Python
thread ran -- its two children ``teardown-release`` and ``heap-boundary``
(extent, ``cpu_s``, ``gc_s``, hand-offs and sweeps), the rest of the pop
in its two parts (``queue_s`` less ``wait_s``, ``group_s``) and how much
of the pop the parts cover; the share of the cycles that carry every new
name; what the second reading of the thread clocks cost (``read_s``, us a
cycle); and the cycles that break what the record promises
(``violations``).  On a program from before PR 51 every figure is
``null`` and nothing is counted.  Prints ``teardown_report: {...}`` and
then the run's result line, last, as run.py prints it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# clocks are read a few microseconds apart and rounded to one
SLACK_S = 2e-4


def _ms(values, how=statistics.fmean):
    return round(1e3 * how(values), 3) if values else None


def _spread(values):
    """[median, 95th percentile, max] in ms: a mean hides a part that is
    slow one cycle in sixteen."""
    if not values:
        return None
    v = sorted(values)
    return [round(1e3 * v[len(v) // 2], 3),
            round(1e3 * v[min(len(v) - 1, int(0.95 * len(v)))], 3),
            round(1e3 * v[-1], 3)]


def structure(cycles) -> dict:
    """What the cycles say about the inside of ``pop``."""
    from perfbench.lib import spans, teardown, threads
    bad = {k: 0 for k in ("teardowns_a_pop", "extent_off_teardown_s",
                          "child_outside_teardown", "children_overlap",
                          "cpu_over_extent", "parts_over_pop")}
    pop_ms, td_ms, td_arg, serving, rel, rel_cpu = [], [], [], [], [], []
    heap, heap_cpu, heap_gc, queue, group, wait = [], [], [], [], [], []
    cover, read, outcomes = [], [], []
    by_name, n_named, handoffs, sweeps, complete = {}, 0, 0, 0, 0
    for c in cycles:
        pops = spans.named(c, "pop")
        tds = spans.named(c, teardown.SPAN)
        if not pops or not tds:
            continue
        if len(tds) > 1 or tds[0]["parent"] != pops[0]["id"]:
            bad["teardowns_a_pop"] += 1
        pop, td = pops[0], tds[0]
        a, ta = pop["args"], td["args"]
        ext = td["t1"] - td["t0"]
        pop_ext = pop["t1"] - pop["t0"] - a.get("wait_s", 0.0)
        pop_ms.append(pop_ext)
        td_ms.append(ext)
        td_arg.append(a.get("teardown_s", 0.0))
        if abs(ext - a.get("teardown_s", 0.0)) > SLACK_S:
            bad["extent_off_teardown_s"] += 1
        serving.append(ta.get("cpu_s", 0.0))
        if "read_s" in ta:
            read.append(ta["read_s"])
        if teardown.CPU_ARG in ta:
            n_named += 1
            mine = threads.serving_thread(c)
            for k, v in ta[teardown.CPU_ARG].items():
                k = "(serving thread)" if k == mine else k
                by_name[k] = by_name.get(k, 0.0) + v
        kids = sorted((s for s in c["spans"] if s["parent"] == td["id"]),
                      key=lambda s: s["t0"])
        for s in kids:
            if s["t0"] < td["t0"] - SLACK_S or s["t1"] > td["t1"] + SLACK_S:
                bad["child_outside_teardown"] += 1
        for x, y in zip(kids, kids[1:]):
            if y["t0"] < x["t1"] - SLACK_S:
                bad["children_overlap"] += 1
        for s in [td] + kids:
            if s["args"].get("cpu_s", 0.0) > s["t1"] - s["t0"] + SLACK_S:
                bad["cpu_over_extent"] += 1
        for s in kids:
            if s["name"] == teardown.RELEASE_SPAN:
                rel.append(s["t1"] - s["t0"])
                rel_cpu.append(s["args"].get("cpu_s", 0.0))
                outcomes.append(s["args"].get("outcomes", 0))
            elif s["name"] == teardown.HEAP_SPAN:
                heap.append(s["t1"] - s["t0"])
                heap_cpu.append(s["args"].get("cpu_s", 0.0))
                heap_gc.append(s["args"].get("gc_s", 0.0))
                handoffs += s["args"].get("handoff", 0)
                sweeps += s["args"].get("sweep", 0)
        if "queue_s" in a and "group_s" in a:
            q = a["queue_s"] - a.get("wait_s", 0.0)
            queue.append(q)
            group.append(a["group_s"])
            wait.append(a.get("wait_s", 0.0))
            if ext + a["queue_s"] + a["group_s"] > (
                    pop["t1"] - pop["t0"] + SLACK_S):
                bad["parts_over_pop"] += 1
            if pop_ext > 0:
                cover.append((ext + q + a["group_s"]) / pop_ext)
            names = {s["name"] for s in kids}
            if ("cpu_s" in ta and teardown.CPU_ARG in ta
                    and {teardown.RELEASE_SPAN, teardown.HEAP_SPAN} <= names):
                complete += 1
    if not td_ms:
        return {"cycles": len(cycles), "teardowns": 0}
    cpu_ms = {k: round(1e3 * v / n_named, 3)
              for k, v in sorted(by_name.items())} if n_named else None
    idle = (_ms(td_ms) - sum(cpu_ms.values())) if cpu_ms else None
    return {
        "cycles": len(cycles), "teardowns": len(td_ms),
        "complete_share": round(complete / len(cycles), 4),
        "pop_less_wait_ms": _ms(pop_ms),
        "teardown_ms": _ms(td_ms), "teardown_s_arg_ms": _ms(td_arg),
        "teardown_serving_cpu_ms": _ms(serving),
        "teardown_thread_cpu_ms_by_name": cpu_ms,
        "teardown_no_thread_ran_ms": (round(idle, 3)
                                      if idle is not None else None),
        "release_ms": _ms(rel), "release_cpu_ms": _ms(rel_cpu),
        "release_outcomes_mean": (round(statistics.fmean(outcomes), 1)
                                  if outcomes else None),
        "heap_boundary_ms": _ms(heap), "heap_boundary_cpu_ms": _ms(heap_cpu),
        "heap_boundary_gc_ms": _ms(heap_gc),
        "teardown_ms_p50_p95_max": _spread(td_ms),
        "release_ms_p50_p95_max": _spread(rel),
        "heap_boundary_ms_p50_p95_max": _spread(heap),
        "heap_boundary_cpu_ms_p50_p95_max": _spread(heap_cpu),
        "heap_handoffs": handoffs, "heap_sweeps": sweeps,
        "queue_less_wait_ms": _ms(queue), "group_ms": _ms(group),
        "wait_ms": _ms(wait),
        "parts_cover_pop_min_mean": (
            [round(min(cover), 4), round(statistics.fmean(cover), 4)]
            if cover else None),
        "thread_clock_read_us_mean_max": (
            [round(1e6 * statistics.fmean(read), 1),
             round(1e6 * max(read), 1)] if read else None),
        "violations": bad,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from perfbench.lib import drive, spec
    cell = spec.cell(args.workload, ROOT)
    kept = {}

    def keep(**kw):          # what run_cell hands the readers as ctx
        kept.update(kw)
        return SimpleNamespace(**kw)
    drive.SimpleNamespace = keep
    result = drive.run_cell(cell, args.seed, args.seconds, True)
    print("teardown_report: " + json.dumps(structure(kept["cycles"])))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
