"""Text flame summary for flight-recorder traces.

Reads any export of kubetpu's flight recorder:

  * the flat span-list document (FlightRecorder.to_pipeline_doc),
  * a saved /debug/flightz dump (nested per-cycle span trees), or
  * Chrome traceEvents JSON (/debug/flightz?format=chrome)

and prints (1) a per-stage aggregate table — count, total/mean wall
time, share of the trace window, attributed device wait — and (2) the
span tree of the slowest cycles, indented by parent linkage with per-span
durations and thread tags.

Usage:
  python tools/traceview.py TRACE.json [--cycles N] [--threshold-ms M]
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List


def _load_spans(doc) -> List[dict]:
    """Normalize either export to span dicts: stage/cycle/thread/
    span_id/parent_id/start_s/end_s/args."""
    if "spans" in doc:        # pipeline doc (tolerates the pre-recorder
        out = []              # ad-hoc span list: ids/threads optional)
        for i, s in enumerate(doc["spans"]):
            out.append({"stage": s.get("stage", s.get("name", "?")),
                        "cycle": s.get("cycle", 0),
                        "thread": s.get("thread", ""),
                        "span_id": s.get("span_id", i + 1),
                        "parent_id": s.get("parent_id", 0),
                        "start_s": s.get("start_s", 0.0),
                        "end_s": s.get("end_s", s.get("start_s", 0.0)),
                        "args": s.get("args", {})})
        return out
    if "cycles" in doc and isinstance(doc.get("cycles"), list):
        # /debug/flightz dump: nested per-cycle span trees
        out = []
        t_base = min((c["t0"] for c in doc["cycles"]), default=0.0)
        for c in doc["cycles"]:
            for s in c.get("spans", []):
                out.append({"stage": s["name"], "cycle": c["seq"],
                            "thread": s.get("thread", ""),
                            "span_id": s["id"], "parent_id": s["parent"],
                            "start_s": s["t0"] - t_base,
                            "end_s": s["t1"] - t_base,
                            "args": s.get("args", {})})
        return out
    if "traceEvents" in doc:  # Chrome export
        xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        t_base = min((e["ts"] for e in xs), default=0)
        return [{"stage": e["name"],
                 "cycle": e.get("args", {}).get("cycle", 0),
                 "thread": str(e.get("tid", "")),
                 "span_id": e.get("args", {}).get("span_id", 0),
                 "parent_id": e.get("args", {}).get("parent_id", 0),
                 "start_s": (e["ts"] - t_base) / 1e6,
                 "end_s": (e["ts"] - t_base + e.get("dur", 0)) / 1e6,
                 "args": e.get("args", {})} for e in xs]
    raise SystemExit("unrecognized trace document (expected a flight-"
                     "recorder pipeline doc, flightz dump, or Chrome "
                     "traceEvents JSON)")


def _bar(frac: float, width: int = 24) -> str:
    n = max(0, min(width, int(round(frac * width))))
    return "#" * n + "." * (width - n)


def flame_summary(spans: List[dict]) -> str:
    if not spans:
        return "no spans recorded"
    window = (max(s["end_s"] for s in spans)
              - min(s["start_s"] for s in spans)) or 1e-9
    by_stage: Dict[str, List[dict]] = {}
    for s in spans:
        by_stage.setdefault(s["stage"], []).append(s)
    lines = [f"{len(spans)} spans over {window:.3f}s "
             f"({len(set(s['cycle'] for s in spans))} cycles)", "",
             f"{'stage':<44} {'n':>5} {'total_s':>8} {'mean_ms':>8} "
             f"{'dev_wait_s':>10}  share"]
    rows = []
    for stage, ss in by_stage.items():
        total = sum(s["end_s"] - s["start_s"] for s in ss)
        dev = sum(s.get("args", {}).get("device_wait_s", 0.0) for s in ss)
        rows.append((total, stage, ss, dev))
    for total, stage, ss, dev in sorted(rows, reverse=True):
        lines.append(
            f"{stage[:44]:<44} {len(ss):>5} {total:>8.3f} "
            f"{1000 * total / len(ss):>8.1f} {dev:>10.3f}  "
            f"{_bar(total / window)} {100 * total / window:5.1f}%")
    delta = delta_summary(spans)
    if delta:
        lines += ["", delta]
    return "\n".join(lines)


def delta_summary(spans: List[dict]) -> str:
    """One-line incremental-tensorization digest under the stage table:
    how many cycles rode the scatter path (and their p50 updated-row
    count) vs how many fell back to the blessed full resync.  Counted
    from the delta-apply / resync spans so the split matches the
    scheduler's own counters (a pod-axis-growth cycle emits a
    delta-build AND a resync span but applies no scatter — it counts as
    a resync here, exactly like Scheduler.resync_count)."""
    counts = sorted(s["args"]["delta_rows"] for s in spans
                    if s["stage"] == "delta-apply"
                    and "delta_rows" in s.get("args", {}))
    resyncs = sum(1 for s in spans if s["stage"] == "resync")
    if not counts and not resyncs:
        return ""
    p50 = counts[len(counts) // 2] if counts else 0
    return (f"delta-tensorize: {len(counts)} delta cycles "
            f"(rows p50 {p50}), {resyncs} resyncs")


def auction_summary(doc) -> str:
    """One-line auction digest under the stage table: the per-cycle round
    HISTOGRAM (rounds -> cycles), read from cycle meta (Scheduler
    records auction_rounds on every gang cycle)."""
    metas = []
    if isinstance(doc.get("cycle_meta"), list):        # pipeline doc
        metas = [c.get("meta", {}) for c in doc["cycle_meta"]]
    elif isinstance(doc.get("cycles"), list):          # flightz dump
        metas = [c.get("meta", {}) for c in doc["cycles"]]
    rounds = [m["auction_rounds"] for m in metas
              if isinstance(m.get("auction_rounds"), int)]
    if not rounds:
        return ""
    hist: Dict[int, int] = {}
    for r in rounds:
        hist[r] = hist.get(r, 0) + 1
    h = " ".join(f"{r}r:{n}" for r, n in sorted(hist.items()))
    return f"auction rounds: {h} (max {max(rounds)})"


def journal_summary(doc) -> str:
    """One-line durable-journal digest under the stage table: record and
    byte counts, drops, the recorded cycle window, and the linkage
    hit-rates into the flight-recorder/decision rings — read from the
    "journal" block the pipeline doc (or a /debug/journal dump) carries
    when KUBETPU_JOURNAL was armed for the run (kubetpu/utils/
    journal.py; replay with python -m tools.kubereplay <dir>)."""
    j = doc.get("journal")
    if not isinstance(j, dict) or not j.get("armed"):
        return ""
    kb = j.get("bytes", 0) / 1024.0
    parts = [f"{j.get('records', 0)} records ({kb:.1f} KiB"
             + (f", {j['dropped_total']} dropped"
                if j.get("dropped_total") else "") + ")"]
    span = j.get("cycle_span")
    if span:
        parts.append(f"cycles {span[0]}-{span[1]}")
    if "flight_live_rate" in j:
        parts.append(f"flight-link {100 * j['flight_live_rate']:.0f}%")
    elif "flight_link_rate" in j:
        parts.append(f"flight-link {100 * j['flight_link_rate']:.0f}%")
    if "decision_live_rate" in j:
        parts.append(f"decision-link {100 * j['decision_live_rate']:.0f}%")
    return "journal: " + ", ".join(parts)


def pipeline_summary(doc) -> str:
    """One-line depth-k pipeline digest under the stage table: the
    configured depth plus the ring-slot occupancy histogram (slot ->
    cycles) read from cycle meta — slot 0 is a cycle dispatched straight
    behind a commit, higher slots are cycles parked deeper in the
    in-flight ring, so a spread across slots IS the overlap the depth-k
    executor (kubetpu/pipeline.py) recovers."""
    metas = []
    if isinstance(doc.get("cycle_meta"), list):        # pipeline doc
        metas = [c.get("meta", {}) for c in doc["cycle_meta"]]
    elif isinstance(doc.get("cycles"), list):          # flightz dump
        metas = [c.get("meta", {}) for c in doc["cycles"]]
    slots = [m["ring_slot"] for m in metas
             if isinstance(m.get("ring_slot"), int)]
    if not slots:
        return ""
    depth = max((m.get("pipeline_depth") for m in metas
                 if isinstance(m.get("pipeline_depth"), int)), default=0)
    hist: Dict[int, int] = {}
    for s in slots:
        hist[s] = hist.get(s, 0) + 1
    occ = " ".join(f"slot{k}:{n}" for k, n in sorted(hist.items()))
    return f"pipeline: depth {depth}, ring occupancy {occ}"


def cycle_tree(spans: List[dict], cycle: int,
               threshold_ms: float = 0.0) -> str:
    cs = [s for s in spans if s["cycle"] == cycle]
    by_parent: Dict[int, List[dict]] = {}
    for s in cs:
        by_parent.setdefault(s["parent_id"], []).append(s)
    for kids in by_parent.values():
        kids.sort(key=lambda s: s["start_s"])
    known = {s["span_id"] for s in cs}
    lines = [f"cycle {cycle}:"]

    def walk(parent: int, depth: int) -> None:
        for s in by_parent.get(parent, []):
            dur_ms = 1000 * (s["end_s"] - s["start_s"])
            if dur_ms < threshold_ms and depth > 1:
                continue
            extra = ""
            dev = s.get("args", {}).get("device_wait_s")
            if dev:
                extra = f"  [device_wait {1000 * dev:.1f}ms]"
            thread = s.get("thread", "")
            lines.append(f"  {'  ' * depth}{s['stage']:<40} "
                         f"{dur_ms:>9.1f}ms  ({thread}){extra}")
            walk(s["span_id"], depth + 1)

    # roots: parent 0 or parent outside this cycle's recorded set
    roots = sorted({s["parent_id"] for s in cs
                    if s["parent_id"] == 0 or s["parent_id"] not in known})
    for r in roots:
        walk(r, 0)
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="traceview",
        description="text flame summary for kubetpu flight-recorder "
                    "traces")
    ap.add_argument("trace")
    ap.add_argument("--cycles", type=int, default=2,
                    help="show the span tree of the N slowest cycles")
    ap.add_argument("--threshold-ms", type=float, default=0.5,
                    help="hide sub-spans shorter than this in the trees")
    args = ap.parse_args(argv)
    with open(args.trace) as f:
        doc = json.load(f)
    spans = _load_spans(doc)
    print(flame_summary(spans))
    auction = auction_summary(doc)
    if auction:
        print(auction)
    pipe = pipeline_summary(doc)
    if pipe:
        print(pipe)
    jnl = journal_summary(doc)
    if jnl:
        print(jnl)
    if not spans:
        return 0
    wall: Dict[int, float] = {}
    for s in spans:
        wall[s["cycle"]] = max(wall.get(s["cycle"], 0.0),
                               s["end_s"]) - 0.0
    span_of = {c: min(s["start_s"] for s in spans if s["cycle"] == c)
               for c in wall}
    slowest = sorted(wall, key=lambda c: wall[c] - span_of[c],
                     reverse=True)[:max(args.cycles, 0)]
    for c in slowest:
        print()
        print(cycle_tree(spans, c, threshold_ms=args.threshold_ms))
    return 0


if __name__ == "__main__":
    sys.exit(main())
