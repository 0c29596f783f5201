"""CLI: ``python -m tools.kubeexact [--write | --check] [--json]``.

--write      re-prove the registry and regenerate EXACT_MANIFEST.json
--check      pure-JSON CI gate: re-validate the committed manifest
             without jax (margins, proof statuses,
             environment pin, COMPILE_MANIFEST key join) — safe in
             ci_lint.sh before any jax import
(default)    full gate: re-prove everything, fail on any unsuppressed
             finding or on drift against the committed manifest in
             either direction
--json       machine-readable report on stdout
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeexact")
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--write", action="store_true",
                      help="re-prove and regenerate EXACT_MANIFEST.json")
    mode.add_argument("--check", action="store_true",
                      help="pure-JSON validation of the committed "
                           "manifest (no jax)")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--manifest", default=None,
                    help="manifest path override (tests)")
    args = ap.parse_args(argv)

    from .manifest import (MANIFEST_PATH, build_manifest, check_manifest,
                           diff_manifest, load_manifest, write_manifest)
    path = args.manifest or MANIFEST_PATH

    if args.check:
        fails = check_manifest(load_manifest(path))
        ok = not fails
        report = {"op": "check", "manifest": path, "failures": fails,
                  "clean": ok}
        if args.json:
            print(json.dumps(report, indent=2, sort_keys=True))
        else:
            for f in fails:
                print("exact-check: " + f)
            print("kubeexact check: %s" % ("clean" if ok else "FAILED"))
        return 0 if ok else 1

    from .driver import run_exact
    res = run_exact()
    doc = build_manifest(res)

    if args.write:
        out = write_manifest(doc, path)
        ok = res.clean
        report = {"op": "write", "written": out,
                  "programs": len(doc["programs"]),
                  "findings": [f.to_json() for f in res.findings],
                  "suppressed": [f.to_json() for f in res.suppressed]}
    else:
        drift = diff_manifest(doc, load_manifest(path))
        ok = (res.clean and not drift["added"] and not drift["removed"]
              and not drift["changed"]
              and not drift.get("missing_manifest"))
        report = {"op": "gate", "manifest": path,
                  "programs": len(doc["programs"]),
                  "headroom": res.headroom, "drift": drift,
                  "findings": [f.to_json() for f in res.findings],
                  "suppressed": [f.to_json() for f in res.suppressed],
                  "clean": ok}

    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        if args.write:
            print("wrote %s (%d programs)"
                  % (report["written"], report["programs"]))
        else:
            d = report["drift"]
            if d.get("missing_manifest"):
                print("no committed manifest at %s — run --write" % path)
            for kind in ("added", "removed", "changed"):
                for rid in d.get(kind, []):
                    print("drift(%s): %s" % (kind, rid))
            hr = res.headroom
            print("headroom: min margin %sx (floor %gx) — %s"
                  % (hr.get("min_margin"), hr.get("floor"),
                     hr.get("dominating") or "no float sums"))
        for f in res.findings:
            print(str(f))
        for f in res.suppressed:
            print(str(f))
        if not args.write:
            print("kubeexact: %s (%d programs)"
                  % ("clean" if ok else "FINDINGS/DRIFT",
                     report["programs"]))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
