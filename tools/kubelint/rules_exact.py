"""exact/* — exact-reduction discipline rules.

The bit-match contract survives multi-chip execution only
because every cross-shard reduction is drawn from a blessed
set (ops/kernels.py): float max/min (exactly associative), integer-valued
f32 sums proven below 2**24 (tools/kubeexact), and the gumbel-decomposed
tie-broken argmax.  tools/kubeexact proves the *traced* programs obey the
discipline; these rules keep the *source* from growing new raw call sites
that would bypass the blessed helpers (and thus the prover's contract
docstrings and the manifest's audited surface).

Rules:

  exact/raw-collective-reduce   lax.psum/pmax/pmin called outside
                                ops/kernels.py — route cross-axis
                                reductions through exact_psum/exact_pmax/
                                exact_pmin so every collective site names
                                its exactness contract.
  exact/raw-tie-argmax          jnp.argmax/argmin in a shard_map
                                module outside the blessed
                                helpers — tie-broken selections must use
                                gumbel_tiebreak_argmax /
                                crossaxis_first_index_argmax (ties replay
                                selectHost bit-for-bit; see
                                tools/kubeexact/README.md).
"""

from __future__ import annotations

import ast
from typing import List

from .core import Finding, SourceModule

# the blessed-helper home: raw lax collectives / argmax are legal here
_BLESSED_MODULE = "kubetpu.ops.kernels"

_RAW_COLLECTIVES = {
    "jax.lax.psum": "exact_psum",
    "jax.lax.pmax": "exact_pmax",
    "jax.lax.pmin": "exact_pmin",
}

# modules whose argmax sites feed cross-axis selections (the shard_map
# auction): a raw argmax here is a tie-break hazard, not a local utility
_SELECTION_MODULES = ("kubetpu.parallel.shardmap",)

_ARGMAX = {"jax.numpy.argmax", "numpy.argmax", "jax.numpy.argmin",
           "numpy.argmin"}


def check(module: SourceModule, ctx) -> List[Finding]:
    cg = ctx.callgraph
    mi = cg.module_info(module)
    out: List[Finding] = []
    if module.name == _BLESSED_MODULE:
        return out

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = cg.resolve_dotted(mi, node.func) or ""

        if dotted in _RAW_COLLECTIVES:
            out.append(Finding(
                "exact/raw-collective-reduce", module.path, node.lineno,
                node.col_offset + 1,
                "%s called directly — cross-axis reductions go through "
                "ops/kernels.py:%s so the call site names its exactness "
                "contract (float max/min or int-valued sum < 2**24, "
                "proven by tools/kubeexact)" % (
                    dotted.replace("jax.lax", "lax"),
                    _RAW_COLLECTIVES[dotted])))

        if dotted in _ARGMAX and module.name in _SELECTION_MODULES:
            out.append(Finding(
                "exact/raw-tie-argmax", module.path, node.lineno,
                node.col_offset + 1,
                "raw argmax in a cross-axis selection module — ties must "
                "replay selectHost bit-for-bit via the gumbel "
                "decomposition (ops/kernels.py:gumbel_tiebreak_argmax / "
                "crossaxis_first_index_argmax)"))
    return out
