"""COMPILE_MANIFEST.json: serialization and drift diffing.

The committed manifest is the version-controlled compile surface.  CI
(``python -m tools.kubecensus --check``) regenerates the rows in memory
and fails on drift in either direction — a traced variant absent from
the committed file (surface grew silently) or a committed row no trace
reproduces (dead ladder bucket).
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

MANIFEST_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "COMPILE_MANIFEST.json")

def row_id(row: dict) -> str:
    tag = ":" + row["tag"] if row.get("tag") else ""
    return "%s%s@%s" % (row["program"], tag, row["variant"])


def write_manifest(rows: List[dict], path: str = None) -> str:
    """Deterministic serialization: sorted rows, sorted keys, fixed
    indent, trailing newline — regeneration over an unchanged tree is
    byte-identical."""
    path = path or MANIFEST_PATH
    doc = {
        "_comment": "Compile-surface census (tools/kubecensus). "
                    "Regenerate: make census (python -m tools.kubecensus "
                    "--write). CI fails on drift in either direction.",
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    return path


def load_manifest(path: str = None) -> Optional[List[dict]]:
    path = path or MANIFEST_PATH
    try:
        with open(path) as f:
            return json.load(f)["rows"]
    except (OSError, ValueError, KeyError):
        return None


def diff_manifest(current: List[dict],
                  committed: Optional[List[dict]]) -> Dict[str, list]:
    """Three-way drift: added (traced, not committed), removed (committed,
    not reproduced — a dead ladder bucket), changed (same id, different
    trace: avals, jaxpr hash, donation or statics moved)."""
    if committed is None:
        return {"added": [row_id(r) for r in current], "removed": [],
                "changed": [], "missing_manifest": True}
    cur = {row_id(r): r for r in current}
    com = {row_id(r): r for r in committed}
    added = sorted(set(cur) - set(com))
    removed = sorted(set(com) - set(cur))
    changed = []
    watched = ("qualname", "in_avals", "compiled_in_avals", "out_avals",
               "lowering_sha256", "donation", "static_sig", "sharding")
    for rid in sorted(set(cur) & set(com)):
        for k in watched:
            if cur[rid].get(k) != com[rid].get(k):
                changed.append("%s (%s)" % (rid, k))
                break
    return {"added": added, "removed": removed, "changed": changed}
