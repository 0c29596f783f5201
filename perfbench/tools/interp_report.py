#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, plus what
the result line does not say about who held the interpreter (PR 38):

    python3 perfbench/tools/interp_report.py --workload <cell> --seed <n> --seconds <s>

For the in-window cycles: the CPU of every Python thread by NAME, ms a
cycle, beside the cycle's period; the lane's ``bind-job`` span against the
bind table's lane rows (does its extent hold them, how far is it from
what ``lane_busy_ms_per_cycle.sat`` reads) with its ``cpu_s``, ``wake_s``
and ``settle_s``; the hand-over's wait against ``submit_s``; the
collector's passes a cycle, its pauses by the phase they fell on and
every full collection's event; what of ``tensorize`` no child span
covers; and the record's own health (most spans and events a cycle,
drops, the phases' ``cpu_s`` summed against the serving thread's entry of
``thread_cpu_s``).  ``violations`` counts the cycles that break what the
program promises; on a program from before PR 38 the report says
``null`` and counts none.  Prints ``interp_report: {...}`` and then the
run's result line, last, as run.py prints it.
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

TENSORIZE_CHILDREN = ("delta-build", "delta-apply", "verify", "resync",
                      "batch-build", "row-maps", "batch-upload")
# clocks are read a few microseconds apart and rounded to one
SLACK_S = 2e-4


def _ms(values, how=statistics.fmean):
    return round(1e3 * how(values), 3) if values else None


def structure(cycles) -> dict:
    """What the cycles say about the interpreter, and the cycles that
    break a promise (``violations``: name -> count)."""
    from perfbench.lib import spans, threads
    bad = {k: 0 for k in ("jobs_a_cycle", "job_misses_lane_rows",
                          "job_cpu_over_extent", "handover_over_submit",
                          "gc_over_extent", "phase_cpu_off_thread_cpu")}
    job, busy_gap, wake, settle, job_cpu = [], [], [], [], []
    wait, submit, ratio, uncovered, passes = [], [], [], [], []
    gc_by_span, full_by_span, gc_events = {}, {}, []
    says_jobs = any(spans.named(c, threads.JOB_SPAN) for c in cycles)
    for c in cycles:
        jobs = spans.named(c, threads.JOB_SPAN)
        rows = [(r[1], r[2]) for r in c.get("binds", ())
                if len(r) > 3 and r[3] == spans.LANE_THREAD
                and r[1] > 0.0 and r[2] > 0.0]
        if len(jobs) > 1 or (rows and not jobs and says_jobs):
            bad["jobs_a_cycle"] += 1
        for j in jobs:
            ext = j["t1"] - j["t0"]
            job.append(ext)
            job_cpu.append(j["args"]["cpu_s"])
            if "wake_s" in j["args"]:
                wake.append(j["args"]["wake_s"])
            settle.append(j["args"].get("settle_s", 0.0))
            if j["args"]["cpu_s"] > ext + SLACK_S:
                bad["job_cpu_over_extent"] += 1
            mine = rows if j["thread"] == spans.LANE_THREAD else []
            if mine:
                lo, hi = min(s for s, _ in mine), max(d for _, d in mine)
                busy_gap.append(ext - (hi - lo))
                if lo < j["t0"] - SLACK_S or hi > j["t1"] + SLACK_S:
                    bad["job_misses_lane_rows"] += 1
        for s in c["spans"]:
            a = s["args"]
            if "gc_s" in a:
                gc_by_span[s["name"]] = gc_by_span.get(s["name"], 0.0) \
                    + a["gc_s"]
                if a["gc_s"] > s["t1"] - s["t0"] + SLACK_S:
                    bad["gc_over_extent"] += 1
            if "gc_full" in a:
                full_by_span[s["name"]] = full_by_span.get(s["name"], 0) \
                    + a["gc_full"]
        gc_events += [dict(e["args"], thread=e["thread"])
                      for e in c.get("events", ()) if e["name"] == "gc"]
        for s in spans.named(c, "commit"):
            if "handover_wait_s" in s["args"]:
                wait.append(s["args"]["handover_wait_s"])
                submit.append(s["args"]["submit_s"])
                if wait[-1] > submit[-1] + SLACK_S:
                    bad["handover_over_submit"] += 1
        cpu = c["meta"].get(threads.CPU_META)
        if cpu is not None:
            ph = sum(s["args"].get("cpu_s", 0.0) for s in c["spans"]
                     if s["name"] in spans.PHASES)
            mine = cpu.get(threads.serving_thread(c), 0.0)
            if mine > 0:
                ratio.append(ph / mine)
                if abs(ratio[-1] - 1.0) > 0.05:
                    bad["phase_cpu_off_thread_cpu"] += 1
        if "gc_collections" in c["meta"]:
            passes.append(c["meta"]["gc_collections"])
        tz = spans.named(c, "tensorize")
        if tz and spans.named(c, "row-maps"):
            kids = sum(s["t1"] - s["t0"] for s in c["spans"]
                       if s["name"] in TENSORIZE_CHILDREN)
            uncovered.append(sum(s["t1"] - s["t0"] for s in tz) - kids)
    ctx = SimpleNamespace(cycles=cycles)
    return {
        "cycles": len(cycles),
        "max_spans": max((len(c["spans"]) for c in cycles), default=0),
        "max_events": max((len(c.get("events", ())) for c in cycles),
                          default=0),
        "span_drops": sum(c.get("span_drops", 0) for c in cycles),
        "event_drops": sum(c.get("event_drops", 0) for c in cycles),
        "thread_cpu_ms_by_name": {
            k: round(v, 3)
            for k, v in threads.thread_cpu_ms_by_name(cycles).items()},
        "python_cpu_ms": threads.thread_cpu_ms_per_cycle(ctx),
        "phase_cpu_over_thread_cpu_min_median_max": (
            [round(min(ratio), 4), round(statistics.median(ratio), 4),
             round(max(ratio), 4)] if ratio else None),
        "bind_job_ms": _ms(job), "bind_job_cpu_ms": _ms(job_cpu),
        "bind_job_wake_ms_mean_max": [_ms(wake), _ms(wake, max)],
        "bind_job_settle_ms": _ms(settle),
        "bind_job_less_lane_busy_ms_mean_max": [_ms(busy_gap),
                                                _ms(busy_gap, max)],
        "handover_wait_ms": _ms(wait), "submit_ms": _ms(submit),
        "gc_collections_per_cycle": (round(statistics.fmean(passes), 2)
                                     if passes else None),
        "gc_pause_ms": threads.gc_pause_ms_per_cycle(ctx),
        "gc_ms_per_cycle_by_span": {
            k: round(1e3 * v / len(cycles), 3)
            for k, v in sorted(gc_by_span.items())},
        "gc_other_ms": _ms([c["meta"].get("gc_other_s", 0.0)
                            for c in cycles]) if passes else None,
        "gc_full_by_span": full_by_span,
        "gc_events": gc_events[:32],
        "tensorize_under_no_child_ms": _ms(uncovered),
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
    print("interp_report: " + json.dumps(structure(kept["cycles"])))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
