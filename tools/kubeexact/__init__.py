"""kubeexact: a jaxpr-level exactness prover + collective surface
census for the mesh roots in the kubecensus registry.

The bit-match contract of the hottest reductions in the system — "gumbel
decomposition, integer-exact cross-shard sums, first-index argmax" — is
enforced at runtime only by bit-match oracles that need a drained world to
fire.  kubeexact proves the discipline statically, per traced jaxpr:

  * every cross-shard (``psum``/``pmax``/``pmin``) float reduction is
    either a float max/min (exactly associative) or an integer-valued
    sum whose value-range bound stays below 2**24 — proven by an
    integer-valuedness + interval lattice (absint.py) propagated from input avals and registry-declared input
    facts, with symbolic bounds evaluated at north-star shapes
    (northstar.py);
  * the collective surface (op, axis names, dtype, reduce kind, operand
    bytes per pow2-ladder rung) is a committed, drift-gated artifact
    (EXACT_MANIFEST.json) exactly like COMPILE_MANIFEST.json;
  * cross-shard row-gathers inside shard_map bodies and raw tie-broken
    argmax (no gumbel decomposition) are findings, with audited
    ``(rule, reason)`` exemptions on registry entries and stale exemptions
    flagged like kubecensus.
"""

from .bounds import Expr, INT_EXACT_LIMIT  # noqa: F401
