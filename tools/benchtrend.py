"""Bench-trend tooling: a series of saved bench runs as a per-case trend
table, with per-stage regression ATTRIBUTION.

A bench run saved with ``BENCH_OUT=<path>`` carries the bench.py
``detail`` document — and, since the SLO layer (kubetpu/utils/slo.py),
a per-case ``latency`` block (``pod_e2e_p50/p90/p99_s`` +
``stage_shares``).  No run is committed to the repo (the pre-PR-1 rounds
were deleted in PR 23; the driver's PERF_LEDGER.jsonl is the record
now), so the default trajectory is empty: point ``--glob`` at saved
runs, oldest first by name, and/or append a fresh one with ``--run``.
The tool prints:

  * a per-case trend table (pods/s per round, with the round-over-round
    delta), and
  * for every case whose throughput regressed beyond the threshold,
    WHICH STAGE's latency share grew — the stage_shares diff when both
    rounds carry the latency block, the host_share/device_wait split
    otherwise — and, when both rounds carry a devstats ``device`` block
    (kubetpu/utils/devstats.py), WHICH PROGRAM regressed: the one whose
    achieved roofline fraction fell, or whose resident HBM grew.

``--check`` is the CI mode (tools/ci_lint.sh): nonzero exit when a
run is schema-INCOMPATIBLE (a case present but non-numeric where the
trend table needs numbers) or when the newest parseable round regresses
beyond the NORTHSTAR.json gate (bench.py's northstar_gate — the same
floors/ceilings BENCH_GATE=1 enforces).  On an empty trajectory it
degrades to the NORTHSTAR.json schema check.  Runs whose detail cannot
be recovered (e.g. a tail-truncated capture) are reported and skipped,
never a hard failure.

Usage:
  python -m tools.benchtrend [--glob 'runs/BENCH_*.json'] [--run FRESH.json]
                             [--check] [--threshold 0.1]
"""
from __future__ import annotations

import argparse
import glob as globmod
import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# dotted case -> the numeric field the trend table tracks (first match
# wins; cases carrying neither are skipped).  steady_p99_s is the
# sustained_load case's windowed steady-state pod e2e p99
# (kubetpu/utils/telemetry.py) — a seconds row like the restart SLOs.
THROUGHPUT_KEYS = ("pods_per_sec",)
SECONDS_KEYS = ("e2e_best_s", "e2e_s", "restart_s", "cold_restart_s",
                "steady_p99_s")


def _find_detail(doc) -> Optional[Dict[str, Any]]:
    """Recover the bench ``detail`` document from any committed artifact
    shape: a BENCH_OUT file ({"headline", "detail"}), a raw
    {"detail": ...} stderr line, or the round-capture wrapper
    ({"parsed": {"detail": ...}, "tail": "..."}).  Falls back to
    scanning the captured tail for a parseable {"detail": ...} line
    (r05's tail was cut mid-line — that one stays unrecoverable and the
    caller reports it)."""
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("detail"), dict):
        return doc["detail"]
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and isinstance(parsed.get("detail"), dict):
        return parsed["detail"]
    tail = doc.get("tail")
    if isinstance(tail, str):
        for line in tail.splitlines():
            idx = line.find('{"detail"')
            if idx < 0:
                continue
            try:
                cand = json.loads(line[idx:])
            except ValueError:
                continue
            if isinstance(cand.get("detail"), dict):
                return cand["detail"]
    return None


def load_round(path: str) -> Dict[str, Any]:
    name = os.path.basename(path)
    for suffix in (".json",):
        if name.endswith(suffix):
            name = name[: -len(suffix)]
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return {"round": name, "detail": None,
                "note": f"unreadable ({e.__class__.__name__})"}
    detail = _find_detail(doc)
    if detail is None:
        return {"round": name, "detail": None,
                "note": "no parseable detail document "
                        "(truncated capture or non-bench artifact)"}
    return {"round": name, "detail": detail, "note": ""}


def flatten_cases(detail: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Dotted case name -> case dict for every bench case that carries a
    trendable number (top level, chain_drain.* and northstar.*)."""
    out: Dict[str, Dict[str, Any]] = {}

    def visit(prefix: str, node, depth: int) -> None:
        if not isinstance(node, dict):
            return
        has_metric = any(isinstance(node.get(k), (int, float))
                         for k in THROUGHPUT_KEYS + SECONDS_KEYS)
        if has_metric:
            out[prefix] = node
            return
        if depth >= 2:
            return
        for k, v in node.items():
            if isinstance(v, dict):
                visit(f"{prefix}.{k}" if prefix else k, v, depth + 1)

    visit("", detail, 0)
    return out


def case_value(case: Dict[str, Any],
               unit: str = "") -> Tuple[Optional[float], str]:
    """(value, unit) — throughput preferred, seconds as fallback.  Pass
    ``unit`` to pin the extraction to one unit (rows must not mix
    pods/s from one round with seconds from another)."""
    if unit in ("", "pods/s"):
        for k in THROUGHPUT_KEYS:
            v = case.get(k)
            if isinstance(v, (int, float)):
                return float(v), "pods/s"
    if unit in ("", "s"):
        for k in SECONDS_KEYS:
            v = case.get(k)
            if isinstance(v, (int, float)):
                return float(v), "s"
    return None, ""


def row_unit(cases: List[Dict[str, Any]]) -> str:
    """One unit per trend row: pods/s when any round carries it."""
    for case in cases:
        if any(isinstance(case.get(k), (int, float))
               for k in THROUGHPUT_KEYS):
            return "pods/s"
    return "s"


def device_attribution(prev: Dict[str, Any],
                       cur: Dict[str, Any]) -> str:
    """Device-side half of the attribution (the devstats ``device``
    block, kubetpu/utils/devstats.py): name the PROGRAM whose achieved
    roofline fraction fell the most — or slowed the most when neither
    round carries a roofline join — and whether resident HBM grew, so a
    regression reads "run_auction's achieved fraction fell" instead of
    just "the device stage grew"."""
    dp = prev.get("device") or {}
    dc = cur.get("device") or {}
    pp, pc = dp.get("programs") or {}, dc.get("programs") or {}
    notes = []
    worst = None
    for name in sorted(set(pp) & set(pc)):
        f0 = pp[name].get("roofline_fraction")
        f1 = pc[name].get("roofline_fraction")
        if isinstance(f0, (int, float)) and isinstance(f1, (int, float)) \
                and f0 > 0:
            drop = (f0 - f1) / f0
        else:
            m0 = pp[name].get("mean_s")
            m1 = pc[name].get("mean_s")
            if not (isinstance(m0, (int, float))
                    and isinstance(m1, (int, float)) and m0 > 0):
                continue
            drop = (m1 - m0) / m0      # slower mean ~ fallen fraction
            f0 = f1 = None
        if drop > 0.1 and (worst is None or drop > worst[1]):
            worst = (name, drop, f0, f1,
                     pp[name].get("mean_s"), pc[name].get("mean_s"))
    if worst is not None:
        name, _drop, f0, f1, m0, m1 = worst
        if f0 is not None:
            notes.append(f"program '{name}' achieved fraction fell "
                         f"{f0:.4f} -> {f1:.4f}")
        else:
            notes.append(f"program '{name}' device time grew "
                         f"{1000 * m0:.1f} -> {1000 * m1:.1f} ms")
    b0, b1 = dp.get("ledger_bytes"), dc.get("ledger_bytes")
    if isinstance(b0, (int, float)) and isinstance(b1, (int, float)) \
            and b0 > 0 and b1 > b0 * 1.1:
        notes.append(f"resident HBM grew {int(b0)} -> {int(b1)} bytes "
                     f"(+{100 * (b1 - b0) / b0:.0f}%)")
    return "; ".join(notes)


def attribute_regression(prev: Dict[str, Any],
                         cur: Dict[str, Any]) -> str:
    """Name the stage whose share of per-pod latency grew most between
    two rounds of one case — the SLO layer's stage_shares when both
    carry it, the host/device split otherwise — plus the device-side
    attribution (device_attribution) when both rounds carry a devstats
    ``device`` block.  Config deltas are named FIRST — a mesh_shape or
    pipeline-depth change between the rounds is a config delta, not a
    stage regression — so "mesh_shape changed" leads the line before
    any stage-share diff."""
    note = ""
    ms0, ms1 = prev.get("mesh_shape"), cur.get("mesh_shape")
    if ms0 != ms1 and (ms0 is not None or ms1 is not None):
        def _ms(v):
            return "x".join(str(x) for x in v) if isinstance(
                v, (list, tuple)) else ("none" if v is None else str(v))
        note = f"mesh_shape changed {_ms(ms0)} -> {_ms(ms1)}; "
    pd0, pd1 = prev.get("pipeline_depth"), cur.get("pipeline_depth")
    if (isinstance(pd0, (int, float)) and isinstance(pd1, (int, float))
            and pd0 != pd1):
        note += f"pipeline_depth changed {int(pd0)} -> {int(pd1)}; "
    # recovery-path growth is named BEFORE stage shares: on the
    # sustained_load case (and node_flap) a steady-state p99 regression
    # that coincides with the recovery ladder firing more often is a
    # resilience-path regression, not a hot-path one
    for key in ("demotions", "recoveries"):
        r0, r1 = prev.get(key), cur.get(key)
        if (isinstance(r0, (int, float)) and isinstance(r1, (int, float))
                and r1 > r0):
            note += f"{key} grew {int(r0)} -> {int(r1)}; "
    dev = device_attribution(prev, cur)
    dev = ("; " + dev) if dev else ""
    ps = (prev.get("latency") or {}).get("stage_shares") or {}
    cs = (cur.get("latency") or {}).get("stage_shares") or {}
    if ps and cs:
        deltas = {k: cs.get(k, 0.0) - ps.get(k, 0.0)
                  for k in set(ps) | set(cs)}
        stage = max(deltas, key=lambda k: deltas[k])
        if deltas[stage] > 0:
            return note + (f"stage '{stage}' share grew "
                           f"{ps.get(stage, 0.0):.2f} -> "
                           f"{cs.get(stage, 0.0):.2f}"
                           f" (+{deltas[stage]:.2f})") + dev
        return note + "no stage share grew (uniform slowdown)" + dev
    hp, hc = prev.get("host_share"), cur.get("host_share")
    if isinstance(hp, (int, float)) and isinstance(hc, (int, float)):
        side = "host" if hc > hp else "device"
        return note + (f"no latency block on both sides; host_share "
                       f"{hp:.2f} -> {hc:.2f} ({side} side grew)") + dev
    return note + "no latency/host_share data to attribute" + dev


def build_trend(rounds: List[Dict[str, Any]],
                threshold: float) -> Tuple[List[str], List[str], List[str]]:
    """(table lines, attribution lines, schema errors)."""
    usable = [r for r in rounds if r["detail"] is not None]
    per_round = [(r["round"], flatten_cases(r["detail"])) for r in usable]
    names: List[str] = []
    for _, cases in per_round:
        for c in cases:
            if c not in names:
                names.append(c)
    errors: List[str] = []
    width = max([len(n) for n in names] + [4])
    header = f"{'case':<{width}}  " + "  ".join(
        f"{rn[-12:]:>12}" for rn, _ in per_round) + "  unit"
    lines = [header, "-" * len(header)]
    attributions: List[str] = []
    for name in names:
        present = [cases[name] for _, cases in per_round if name in cases]
        unit = row_unit(present)
        vals: List[Optional[float]] = []
        series: List[Tuple[str, Dict[str, Any], float]] = []
        for rn, cases in per_round:
            case = cases.get(name)
            if case is None:
                vals.append(None)
                continue
            v, _ = case_value(case, unit)
            if v is None:
                if case_value(case)[0] is None:
                    errors.append(
                        f"{rn}: case {name!r} present but carries no "
                        f"numeric "
                        f"{'/'.join(THROUGHPUT_KEYS + SECONDS_KEYS)} field")
                vals.append(None)
                continue
            vals.append(v)
            series.append((rn, case, v))
        cells = "  ".join("            " if v is None else f"{v:>12.1f}"
                          for v in vals)
        lines.append(f"{name:<{width}}  {cells}  {unit}")
        # round-over-round regression attribution on adjacent PRESENT
        # rounds (throughput: lower is worse; seconds: higher is worse)
        for (rn0, c0, v0), (rn1, c1, v1) in zip(series, series[1:]):
            if not v0:
                continue
            worse = (v1 < v0 * (1 - threshold) if unit == "pods/s"
                     else v1 > v0 * (1 + threshold))
            if worse:
                attributions.append(
                    f"{name}: {rn0} -> {rn1}: {v0:.1f} -> {v1:.1f} {unit}; "
                    + attribute_regression(c0, c1))
    return lines, attributions, errors


def validate_northstar(path: str) -> List[str]:
    """Schema check of NORTHSTAR.json's gate section that needs NO
    committed round: every entry must carry a numeric pods_per_sec floor
    or seconds ceiling, and its fraction knobs must be numeric.  This is
    what ``--check`` degrades to on an empty trajectory (the repo's
    state since PR 23) — the gate file itself stays validated instead of
    the check erroring out."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except OSError:
        return []          # no NORTHSTAR.json yet: nothing to validate
    except ValueError as e:
        return [f"NORTHSTAR.json unparseable: {e}"]
    gate = doc.get("gate")
    if gate is None:
        return []
    if not isinstance(gate, dict):
        return ["NORTHSTAR.json: 'gate' must be a mapping"]
    errs: List[str] = []
    for key, ref in sorted(gate.items()):
        if not isinstance(ref, dict):
            errs.append(f"gate entry {key!r} must be a mapping")
            continue
        if not any(isinstance(ref.get(f), (int, float))
                   for f in ("pods_per_sec", "seconds")):
            errs.append(f"gate entry {key!r} carries neither a numeric "
                        "pods_per_sec floor nor a seconds ceiling")
        for f in ("min_frac", "max_frac"):
            if f in ref and not isinstance(ref[f], (int, float)):
                errs.append(f"gate entry {key!r}: {f} must be numeric")
        if "path" in ref and not isinstance(ref["path"], str):
            errs.append(f"gate entry {key!r}: path must be a string")
    return errs


def northstar_check(rounds: List[Dict[str, Any]]
                    ) -> Tuple[List[str], str]:
    """Run bench.py's NORTHSTAR gate against the newest parseable
    round's detail — the same floors/ceilings BENCH_GATE=1 enforces,
    minus the live-run-only bit-identity checks.  Returns (failures,
    coverage line): the coverage line says HOW MANY gate entries the
    round actually carried metrics for, so a PASS where every entry was
    skipped reads as 'gate not evaluated', never as a clean bill."""
    latest = next((r for r in reversed(rounds) if r["detail"] is not None),
                  None)
    if latest is None:
        return [], ""
    if REPO_ROOT not in sys.path:
        sys.path.insert(0, REPO_ROOT)
    try:
        from bench import _gate_path, northstar_gate
    except ImportError:
        return [], ""
    path = os.path.join(REPO_ROOT, "NORTHSTAR.json")
    # the trend check gates committed HISTORY, where placements_match
    # booleans may predate the oracle cases — only gate numeric drift
    detail = {k: v for k, v in latest["detail"].items()
              if k != "warm_restart"}
    detail["warm_restart"] = {
        k: v for k, v in (latest["detail"].get("warm_restart") or {}).items()
        if k != "placements_match"}
    # same discipline for the sustained-load contract: the live-run
    # quartet (parity, steady span, demotions, completed_frac) gates
    # BENCH_GATE=1 runs; committed history only trends the steady-p99
    # ceiling
    detail["sustained_load"] = {
        k: v
        for k, v in (latest["detail"].get("sustained_load") or {}).items()
        if k not in ("placements_match", "steady_windows", "demotions",
                     "completed_frac")}
    failures = northstar_gate(detail, path=path)
    try:
        with open(path) as f:
            gate = json.load(f).get("gate") or {}
    except (OSError, ValueError):
        gate = {}
    evaluated = [k for k, ref in gate.items()
                 if _gate_path(detail, ref.get("path", k)) is not None]
    coverage = (f"NORTHSTAR gate on {latest['round']}: "
                f"{len(evaluated)}/{len(gate)} entries evaluated"
                + ("" if evaluated or not gate else
                   " — gate NOT exercised (round carries no gated "
                   "metrics; floors/ceilings bite on BENCH_GATE=1 "
                   "live runs)"))
    return [f"{latest['round']}: {f}" for f in failures], coverage


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="benchtrend",
        description="per-case trend table + regression attribution over "
                    "the committed bench JSON trajectory")
    ap.add_argument("--glob", default="BENCH_*.json",
                    help="comma-separated globs of saved BENCH_OUT runs, "
                         "resolved in the repo root (none is committed)")
    ap.add_argument("--run", default=None,
                    help="a fresh BENCH_OUT-format JSON appended as the "
                         "newest round")
    ap.add_argument("--threshold", type=float, default=0.1,
                    help="relative regression that triggers attribution "
                         "(default 0.1)")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: nonzero exit on schema-incompatible "
                         "artifacts or NORTHSTAR-gate regressions")
    args = ap.parse_args(argv)

    paths: List[str] = []
    for pat in args.glob.split(","):
        pat = pat.strip()
        if not pat:
            continue
        hits = globmod.glob(os.path.join(REPO_ROOT, pat)) or \
            globmod.glob(pat)
        paths.extend(sorted(hits))
    rounds = [load_round(p) for p in paths]
    if args.run:
        rounds.append(load_round(args.run))

    skipped = [r for r in rounds if r["detail"] is None]
    for r in skipped:
        print(f"note: {r['round']}: {r['note']}")
    if not any(r["detail"] is not None for r in rounds):
        # empty (or fully unparseable) trajectory: degrade gracefully —
        # an empty repo history is a state, not an error.  --check still
        # validates the NORTHSTAR gate schema so the floors/ceilings
        # file can't rot while there are no rounds to trend.
        print("no trajectory (no parseable saved bench run matched)")
        if args.check:
            errs = validate_northstar(os.path.join(REPO_ROOT,
                                                   "NORTHSTAR.json"))
            for e in errs:
                print("schema error: " + e)
            if errs:
                return 1
            print("benchtrend --check: PASS (no trajectory; NORTHSTAR "
                  "gate schema ok)")
        return 0

    lines, attributions, errors = build_trend(rounds, args.threshold)
    print("\n".join(lines))
    if attributions:
        print()
        print("regressions (beyond %.0f%%):" % (100 * args.threshold))
        for a in attributions:
            print("  " + a)
    gate_failures, gate_coverage = northstar_check(rounds)
    if gate_coverage:
        print()
        print(gate_coverage)
    for f in gate_failures:
        print("  " + f)
    if args.check:
        # the gate file's own schema is part of the contract even when
        # every round parsed (same check the empty-trajectory path runs)
        errors = errors + validate_northstar(
            os.path.join(REPO_ROOT, "NORTHSTAR.json"))
        for e in errors:
            print("schema error: " + e)
        if errors or gate_failures:
            return 1
        print("benchtrend --check: PASS "
              f"({sum(1 for r in rounds if r['detail'] is not None)} "
              f"rounds, {len(skipped)} unparseable skipped)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
