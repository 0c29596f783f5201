"""kubecensus: whole-program compile-surface census.

kubelint (tools/kubelint) reasons over Python ASTs; kubecensus reasons
over the TRACED programs themselves.  It discovers every jit root in
``kubetpu/`` (kubelint's call-graph closure cross-checked against an
explicit registry), abstractly traces each root with ``jax.eval_shape`` /
``jit(...).lower()`` across the pow2 bucket ladder, and emits
``COMPILE_MANIFEST.json``: one row per (program x bucket x dtype x
donation x sharding) variant with abstract in/out avals, a stable jaxpr
hash, the donation signature XLA actually honored at lowering, and XLA
cost-analysis FLOPs/bytes.

The manifest is version-controlled.  CI regenerates it in memory and
fails on drift in either direction: a traced variant missing from the
committed manifest (the surface grew — a recompile hazard and an AOT
gap) or a committed row no trace reproduces (a dead ladder bucket —
exactly what AOT prewarm should prune).  The manifest is verbatim the
compile list the AOT pass (tools/kubeaot) feeds to
``lower().compile()``.

On top of the traced jaxprs a semantic rule family runs checks AST lint
cannot express — see tools/kubecensus/README.md for the rule catalog.
"""

from .census import (Finding, audit_entry, audit_callable, run_census,
                     CensusResult)
from .manifest import (MANIFEST_PATH, load_manifest, write_manifest,
                       diff_manifest)
from .registry import ENTRIES, DEFAULT_LADDER, Rung, build_world

__all__ = [
    "Finding", "audit_entry", "audit_callable", "run_census",
    "CensusResult", "MANIFEST_PATH", "load_manifest", "write_manifest",
    "diff_manifest", "ENTRIES", "DEFAULT_LADDER", "Rung", "build_world",
]
