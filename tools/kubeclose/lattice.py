"""The provenance lattice.

Every value that can reach a dispatch seam in a static or
shape-determining position gets a ``Prov``: a label naming its finiteness
class, an optional explicit value set when the class is enumerable, and a
``why`` trail for findings.  Labels, least to greatest:

    const                literal constant(s); ``values`` enumerates them
    bool                 a boolean expression: {True, False}
    registry-enumerated  drawn from a finite in-package vocabulary (a
                         helper whose every return is a literal, an
                         audited config-field domain)
    config-constant      a field/instance of an audited config class
                         (ProgramConfig / KubeSchedulerConfiguration):
                         finite per deployment, symbolic to the prover;
                         ``of`` carries the class name
    mesh-key             a ``register_mesh`` token: one per mesh shape,
                         bounded by the deployment's mesh profiles
    pow2-bucketed        flows through ``utils.intern.pow2_bucket``:
                         member of the pow2 ladder, bounded at north-star
    pad-capacity         ``pow2_bucket`` of a grown capacity (the
                         ``P + B`` pad idiom): the pad ladder, a
                         pow2-bucketed subclass kept distinct because its
                         rungs RUN AHEAD of the current world size
    unbounded            everything else — not provably finite

The join is label-max with value-set union; ``unbounded`` absorbs.  A
join of two enumerable labels stays enumerable (const ⊔ bool and
const ⊔ registry-enumerated are registry-enumerated), which is what lets
``x or "default"`` or a helper returning one of two literals enumerate
instead of widening.

No jax imports anywhere in this package: the full prover runs in the
no-jax CI gate.
"""

from __future__ import annotations

import dataclasses
from typing import FrozenSet, Optional, Tuple

LABELS: Tuple[str, ...] = (
    "const", "bool", "registry-enumerated", "config-constant", "mesh-key",
    "pow2-bucketed", "pad-capacity", "unbounded",
)
_ORDER = {lbl: i for i, lbl in enumerate(LABELS)}

# labels whose value set is explicitly enumerable
_ENUMERABLE = ("const", "bool", "registry-enumerated")

# labels that are finite (closure-safe) without explicit values
FINITE_SYMBOLIC = ("config-constant", "mesh-key", "pow2-bucketed",
                   "pad-capacity")

# canonical reprs jit/Python treat as falsy — dropped by `x or default`
FALSY = frozenset(("None", "False", "0", "0.0", "''", '""'))


@dataclasses.dataclass(frozen=True)
class Prov:
    label: str
    values: Optional[FrozenSet[str]] = None   # canonical reprs, or None
    why: str = ""
    of: str = ""                              # config class for c-c labels

    @property
    def finite(self) -> bool:
        return self.label != "unbounded"

    @property
    def enumerable(self) -> bool:
        return self.label in _ENUMERABLE and self.values is not None

    def to_json(self) -> dict:
        d = {"label": self.label,
             "values": sorted(self.values) if self.values is not None
             else None,
             "why": self.why}
        if self.of:
            d["of"] = self.of
        return d


BOOL = Prov("bool", frozenset(("True", "False")), "boolean expression")
UNBOUNDED = Prov("unbounded", None, "unknown")


def const(values, why: str = "literal") -> Prov:
    return Prov("const", frozenset(values), why)


def unbounded(why: str) -> Prov:
    return Prov("unbounded", None, why)


def canon(v) -> str:
    """Canonical repr used for value sets, closure axes, and the
    registry's ``closure_statics`` metadata — plain ``repr`` so True /
    512 / 'tiled' / None all round-trip through JSON as strings."""
    return repr(v)


def join(a: Optional[Prov], b: Optional[Prov]) -> Optional[Prov]:
    """Least upper bound.  ``None`` is bottom (an unanalyzed branch)."""
    if a is None:
        return b
    if b is None:
        return a
    if a.label == "unbounded":
        return a
    if b.label == "unbounded":
        return b
    lo, hi = (a, b) if _ORDER[a.label] <= _ORDER[b.label] else (b, a)
    if hi.label in _ENUMERABLE:
        # both enumerable: keep the values if both carry them
        values = (a.values | b.values
                  if a.values is not None and b.values is not None
                  else None)
        label = a.label if a.label == b.label else "registry-enumerated"
        if values is None:
            return Prov("unbounded", None,
                        "enumerable label without a value set (%s | %s)"
                        % (a.why, b.why))
        return Prov(label, values, _merge_why(a.why, b.why))
    if hi.label == "config-constant" and lo.label in _ENUMERABLE:
        # a config field joined with a literal default stays the field
        return hi
    if hi.label in ("pow2-bucketed", "pad-capacity", "mesh-key"):
        # a literal default (0, None) joined into a ladder class stays
        # the ladder class — the default is one more rung, not a widening
        if lo.label in _ENUMERABLE or lo.label == hi.label:
            return Prov(hi.label, None, _merge_why(a.why, b.why), hi.of)
        if lo.label in ("pow2-bucketed", "pad-capacity"):
            return Prov("pad-capacity", None, _merge_why(a.why, b.why))
        return Prov("unbounded", None,
                    "incomparable finite classes: %s | %s"
                    % (a.label, b.label))
    if a.label == b.label:
        return Prov(a.label, None, _merge_why(a.why, b.why), a.of)
    return Prov("unbounded", None,
                "incomparable finite classes: %s | %s" % (a.label, b.label))


def _merge_why(a: str, b: str) -> str:
    if not a or a == b:
        return b
    if not b:
        return a
    return "%s | %s" % (a, b)


def drop_falsy(p: Prov) -> Prov:
    """The left side of ``x or default``: its falsy members never reach
    the result."""
    if p.values is None:
        return p
    kept = frozenset(v for v in p.values if v not in FALSY)
    return dataclasses.replace(p, values=kept)


def presence(p: Optional[Prov]) -> Tuple[str, ...]:
    """The {present, absent} axis of an optional dynamic argument
    (host_ok / score_bias / tie_index): a literal None is absent, a
    maybe-None join is both, anything else is present.  Presence changes
    the dispatched program (the call treedef), so it is a closure axis
    even though the argument itself is traced, not static."""
    if p is None:
        return ("absent",)
    if p.values is not None:
        has_none = "None" in p.values
        has_val = bool(p.values - frozenset(("None",)))
        if has_none and has_val:
            return ("absent", "present")
        if has_none:
            return ("absent",)
        return ("present",)
    # non-enumerable (an array, a config product, an unbounded join):
    # conservatively both — the seam's default None keeps absent live
    return ("absent", "present")
