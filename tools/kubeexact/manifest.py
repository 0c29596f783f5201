"""EXACT_MANIFEST.json: serialization, drift diffing, and the pure-JSON
re-validation the no-jax CI gate runs.

The committed manifest is the version-controlled exactness surface —
every proved reduction with its symbolic bound and north-star margin,
the collective surface (operand bytes per ladder rung), and the
committed environment the bounds were evaluated under.
Two consumers:

* CI (``python -m tools.kubeexact``): re-proves the registry and fails
  on drift in either direction — a program or reduction absent from the
  committed file (exactness surface grew silently) or a committed row no
  trace reproduces (dead entry).  Mirrors COMPILE_MANIFEST.json.
* CI without jax (``python -m tools.kubeexact --check``): re-validates
  the committed file alone — margins above the floor, every proof
  exact/exempt, the environment byte-equal to
  tools/kubeexact/northstar.py, and every program key present in
  COMPILE_MANIFEST.json (the exactness surface cannot name a program
  the compile census does not license).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

from . import northstar

MANIFEST_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "EXACT_MANIFEST.json")

_COMMENT = ("Exactness census (tools/kubeexact). Regenerate: make exact "
            "(python -m tools.kubeexact --write). CI fails on drift in "
            "either direction; --check re-validates this file without jax.")


def build_manifest(res) -> dict:
    """ExactResult -> the committed document (plain JSON types only)."""
    programs: Dict[str, dict] = {}
    for r in res.results:
        programs[r.program] = {
            "facts": [list(f) for f in r.facts],
            "exemptions": [list(t) for t in sorted(
                {(f.rule, f.reason or "") for f in r.suppressed})],
            "proofs": r.proofs,
            "surface": r.surface,
        }
    return {
        "_comment": _COMMENT,
        "int_exact_limit": northstar.INT_EXACT_LIMIT,
        "margin_floor": northstar.MARGIN_FLOOR,
        "northstar_env": dict(northstar.NORTHSTAR_ENV),
        "headroom": res.headroom,
        "programs": programs,
    }


def write_manifest(doc: dict, path: str = None) -> str:
    """Deterministic serialization: sorted keys, fixed indent, trailing
    newline — regeneration over an unchanged tree is byte-identical."""
    path = path or MANIFEST_PATH
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def load_manifest(path: str = None) -> Optional[dict]:
    path = path or MANIFEST_PATH
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def diff_manifest(current: dict,
                  committed: Optional[dict]) -> Dict[str, list]:
    """Two-directional drift over program keys plus watched-content
    changes: added (proved, not committed), removed (committed, not
    reproduced), changed (same program, different proofs/surface/
    facts/exemptions — or the committed environment itself moved)."""
    if committed is None:
        return {"added": sorted(current.get("programs", {})),
                "removed": [], "changed": [], "missing_manifest": True}
    cur = current.get("programs", {})
    com = committed.get("programs", {})
    added = sorted(set(cur) - set(com))
    removed = sorted(set(com) - set(cur))
    changed = []
    for key in ("int_exact_limit", "margin_floor", "northstar_env",
                "headroom"):
        if current.get(key) != committed.get(key):
            changed.append("<%s>" % key)
    watched = ("facts", "exemptions", "proofs", "surface")
    for k in sorted(set(cur) & set(com)):
        for w in watched:
            if cur[k].get(w) != com[k].get(w):
                changed.append("%s (%s)" % (k, w))
                break
    return {"added": added, "removed": removed, "changed": changed}


# ---------------------------------------------------------------- --check

_OK_STATUS = ("exact", "exempt")


def check_manifest(doc: Optional[dict],
                   census_path: str = None) -> List[str]:
    """Pure-JSON re-validation of the committed manifest (no jax).
    Returns failure strings; empty means the gate is green."""
    fails: List[str] = []
    if doc is None:
        return ["no committed EXACT_MANIFEST.json — run --write"]
    if doc.get("int_exact_limit") != northstar.INT_EXACT_LIMIT:
        fails.append("int_exact_limit %r != committed constant %r"
                     % (doc.get("int_exact_limit"),
                        northstar.INT_EXACT_LIMIT))
    if doc.get("margin_floor") != northstar.MARGIN_FLOOR:
        fails.append("margin_floor %r != northstar.MARGIN_FLOOR %r"
                     % (doc.get("margin_floor"), northstar.MARGIN_FLOOR))
    if doc.get("northstar_env") != northstar.NORTHSTAR_ENV:
        fails.append("northstar_env drifted from tools/kubeexact/"
                     "northstar.py — regenerate with --write")
    hr = doc.get("headroom") or {}
    mm = hr.get("min_margin")
    if mm is not None and mm < northstar.MARGIN_FLOOR:
        fails.append("headroom min_margin %.4g below the %gx floor (%s)"
                     % (mm, northstar.MARGIN_FLOOR,
                        hr.get("dominating", "?")))
    for key, prog in sorted((doc.get("programs") or {}).items()):
        for p in prog.get("proofs", []):
            if p.get("status") not in _OK_STATUS:
                fails.append("%s: proof %s %s is %r, not exact/exempt"
                             % (key, p.get("op"), p.get("kind"),
                                p.get("status")))
            m = p.get("margin")
            if m is not None and m < northstar.MARGIN_FLOOR:
                fails.append("%s: margin %.4gx below the %gx floor"
                             % (key, m, northstar.MARGIN_FLOOR))
    fails.extend(_check_census_join(doc, census_path))
    return fails


def _check_census_join(doc: dict, census_path: str = None) -> List[str]:
    """Every exactness program must be a program the compile census
    licenses (same key space COMPILE_MANIFEST.json rows use)."""
    from tools.kubecensus.manifest import MANIFEST_PATH as CENSUS_PATH
    path = census_path or CENSUS_PATH
    try:
        with open(path) as f:
            rows = json.load(f)["rows"]
    except (OSError, ValueError, KeyError):
        return ["cannot read COMPILE_MANIFEST.json at %s" % path]
    census_keys = {r["program"] + (":" + r["tag"] if r.get("tag") else "")
                   for r in rows}
    return ["%s: not a COMPILE_MANIFEST program — exactness surface "
            "names an unlicensed root" % k
            for k in sorted(set(doc.get("programs") or {}) - census_keys)]
