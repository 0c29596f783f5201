#!/usr/bin/env python3
"""One traced run of a cell, as ``run.py --trace 1`` makes it, plus what
the result line does not say about the span tree it was read from:

    python3 perfbench/tools/span_report.py --workload <cell> --seed <n> --seconds <s>

For the in-window cycles: the most spans a cycle recorded and the spans
dropped; how much of the serving thread's period (this cycle's ``pop`` to
the next one's) the eight phases cover; how much of the commit loop its
six sums cover; whether every bind the client saw from those cycles has a
complete row in its cycle's bind table.  For the capture: the clock
offset and its spread, and the share of the device-idle time under no
``Scheduling:`` phase.  Prints ``span_report: {...}`` and then the run's
result line, last, as run.py prints it.
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

SUMS = ("recheck_s", "reserve_s", "assume_s", "permit_s", "submit_s",
        "records_s")


def structure(cycles, client) -> dict:
    """The acceptance numbers of the span tree, over ``cycles``."""
    from perfbench.lib import spans
    cover, commit_cover, missing, rows_seen = [], [], [], 0
    gaps = {}          # phase -> seconds between its end and the next's start
    teardown = []      # pop.teardown_s: previous commit's end to pop_batch
    for c, nxt in zip(cycles, cycles[1:] + [None]):
        ph = {s["name"]: s for s in c["spans"] if s["name"] in spans.PHASES}
        if nxt is not None and "pop" in ph:
            nxt_pop = spans.named(nxt, "pop")
            if nxt_pop:
                period = nxt_pop[0]["t0"] - ph["pop"]["t0"]
                cover.append(sum(s["t1"] - s["t0"] for s in ph.values())
                             / period)
                order = [ph[n] for n in spans.PHASES if n in ph] + nxt_pop
                for a, b in zip(order, order[1:]):
                    gaps.setdefault(a["name"], []).append(b["t0"] - a["t1"])
        if "teardown_s" in ph.get("pop", {}).get("args", {}):
            teardown.append(ph["pop"]["args"]["teardown_s"])
        a = ph.get("commit", {}).get("args", {})
        if a.get("loop_s"):
            commit_cover.append(sum(a[k] for k in SUMS) / a["loop_s"])
        for name, row in zip(c["meta"].get("batch_pods", ()),
                             c.get("binds", ())):
            if name in client.bound_t:
                rows_seen += 1
                if not (0.0 < row[0] <= row[1] <= row[2]):
                    missing.append(name)
    return {
        "cycles": len(cycles),
        "max_spans": max((len(c["spans"]) for c in cycles), default=0),
        "span_drops": sum(c["span_drops"] for c in cycles),
        "phase_cover_min": min(cover, default=None),
        "phase_cover_median": statistics.median(cover) if cover else None,
        "gap_after_phase_ms_median_max": {
            k: [round(1e3 * statistics.median(v), 3), round(1e3 * max(v), 3)]
            for k, v in gaps.items()},
        "pop_teardown_ms_median": (round(1e3 * statistics.median(teardown), 3)
                                   if teardown else None),
        "commit_sums_cover_min": min(commit_cover, default=None),
        "binds_seen_by_client": rows_seen,
        "binds_without_complete_row": len(missing),
        "compile_events": [{k: v for k, v in e["args"].items()
                            if k != "shapes"}
                           for c in cycles for e in c["events"]
                           if e["name"] == "xla-compile"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from perfbench.lib import drive, spans, spec, xplane
    cell = spec.cell(args.workload, ROOT)
    kept = {}

    def keep(**kw):          # what run_cell hands the readers as ctx
        kept.update(kw)
        return SimpleNamespace(**kw)
    drive.SimpleNamespace = keep
    result = drive.run_cell(cell, args.seed, args.seconds, True)
    report = structure(kept["cycles"], kept["client"])
    pd = spans.capture_of(SimpleNamespace(cell=cell))
    report["clock"] = spans.clock_offset(pd) if pd is not None else None
    gaps = dict(result["breakdown"]["idle_gaps"])
    idle = sum(gaps.values())
    report["idle_no_phase_share"] = (
        gaps.get(xplane.IDLE_LABEL, 0.0) / idle if idle else None)
    print("span_report: " + json.dumps(report))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
