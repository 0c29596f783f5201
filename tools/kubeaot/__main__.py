"""CLI: ``python -m tools.kubeaot [--build | --check | --shape NxB |
--prune --trace P] [--out DIR] [--json]``.

--build       compile + serialize every COMPILE_MANIFEST variant of the
              seamed serving programs into --out (default artifacts/aot)
              and rewrite the committed tools/kubeaot/AOT_INDEX.json;
              nonzero exit on a capture failure or a lowering-sha
              mismatch vs the manifest (the bit-identity oracle)
--check       (default) pure-JSON CI gate: committed AOT_INDEX.json and
              COMPILE_MANIFEST.json must share the same census-family
              row keys in both directions, and the index must agree
              with the committed compile-surface closure
              (CLOSURE_MANIFEST.json) — an artifact rung the closure
              proves unreachable, or a closure-reachable rung with no
              artifact, is flagged as a prune/closure disagreement.
              No jax, safe in ci_lint.sh
--shape NxB   deploy-shaped capture: run Scheduler.prewarm at N nodes /
              B-pod waves under a capture runtime (what a restart with
              KUBETPU_AOT_DIR set loads from); --ladder K chains
              K dry-run rungs
--prune       drop serving rows whose pod bucket the flight recorder
              never saw (--trace <flightz dump>), census rows the
              manifest no longer carries, and census rows whose rung
              the committed closure proves unreachable (proof-driven:
              observation says what WAS served, the closure says what
              CAN be dispatched)
--json        machine-readable report on stdout
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeaot")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--build", action="store_true",
                      help="compile + serialize the census variants")
    mode.add_argument("--check", action="store_true",
                      help="row-key gate vs COMPILE_MANIFEST.json "
                           "(default)")
    mode.add_argument("--shape", default=None, metavar="NxB",
                      help="deploy-shaped capture, e.g. 1000x1024")
    mode.add_argument("--prune", action="store_true",
                      help="drop artifacts for unserved buckets / dead "
                           "manifest rows")
    ap.add_argument("--out", default=None,
                    help="artifact directory (default artifacts/aot)")
    ap.add_argument("--index", default=None,
                    help="committed index path override (tests)")
    ap.add_argument("--closure", default=None,
                    help="CLOSURE_MANIFEST.json path override (tests)")
    ap.add_argument("--trace", default=None,
                    help="flight-recorder export for --prune bucket data")
    ap.add_argument("--ladder", type=int, default=2,
                    help="--shape: chained prewarm dry-run rungs")
    ap.add_argument("--existing-per-node", type=int, default=2)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)

    from . import build as b
    out_dir = args.out or b.DEFAULT_OUT

    if args.build:
        from kubetpu.utils.compilation import enable_persistent_cache
        enable_persistent_cache()
        report = b.build_census(
            out_dir, commit_index=args.index or b.INDEX_COMMIT_PATH)
        ok = all(r["ok"] and r["sha_match"] for r in report)
        doc = {"op": "build", "out": out_dir, "rows": report, "clean": ok}
    elif args.shape:
        n, _, wave = args.shape.partition("x")
        from kubetpu.utils.compilation import enable_persistent_cache
        enable_persistent_cache()
        rep = b.build_shape(out_dir, int(n), int(wave or 1024),
                            ladder=args.ladder,
                            existing_per_node=args.existing_per_node)
        ok = rep.get("rows", 0) > 0
        doc = {"op": "shape", **rep, "clean": ok}
    elif args.prune:
        rep = b.prune(out_dir, trace_path=args.trace,
                      closure_path=args.closure or b.CLOSURE_PATH)
        ok = "error" not in rep
        doc = {"op": "prune", "out": out_dir, **rep, "clean": ok}
    else:
        failures = b.check_index(args.index or b.INDEX_COMMIT_PATH,
                                 closure_path=args.closure
                                 or b.CLOSURE_PATH)
        ok = not failures
        doc = {"op": "check", "failures": failures, "clean": ok}

    if args.json:
        print(json.dumps(doc, indent=1, sort_keys=True))
    else:
        if args.build:
            for r in doc["rows"]:
                print("%-40s %6.2fs  %s" % (
                    r["row"], r["seconds"],
                    "ok" if r["ok"] and r["sha_match"]
                    else "SHA-MISMATCH" if r["ok"] else "FAILED"))
        elif not ok or doc.get("op") == "check":
            for f in doc.get("failures", []):
                print("aot-index: " + f)
        print("kubeaot %s: %s" % (doc["op"], "clean" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
