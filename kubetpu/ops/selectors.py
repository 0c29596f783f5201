"""Batched label-selector matching as dense tensor ops.

This is the TPU-native replacement for the reference's per-object
``labels.Selector.Matches`` calls scattered across every plugin
(reference: staging/src/k8s.io/apimachinery/pkg/labels/selector.go,
pkg/scheduler/framework/plugins/*/): a *selector* is compiled host-side
into multi-hot vectors over the interned (key,value) / key vocabularies,
and matching S selectors against M targets (nodes or pods) becomes two
batched matmuls on the MXU plus elementwise logic — no per-object string
work on the hot path.

Semantics per requirement (AND across requirements of one selector):
  In(key, vals)      -> target has any interned (key,v) for v in vals
  NotIn(key, vals)   -> negation of In  (key absent also matches)
  Exists(key)        -> target has the key
  DoesNotExist(key)  -> negation of Exists
  Gt/Lt(key, val)    -> numeric parse of the target's label value compared
                        to val; unparsable/missing never matches
matching apimachinery's Requirement.Matches (selector.go:214-260).
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Union

import jax.numpy as jnp
import numpy as np

from ..api import types as api
from ..utils.intern import InternTable, pow2_bucket


class SelectorSet(NamedTuple):
    """S selector *slots* backed by U <= S unique compiled selectors.

    Pods stamped out by one controller share identical selectors, so the
    compiler dedups: the dense requirement tensors are stored once per
    unique selector and each slot carries only an index.  This is the
    difference between O(B x L) and O(U x L) memory/FLOPs for a B-pod batch
    (hollow 100k-pod batches have U in the tens), and it is invisible to
    callers — match_selectors still returns [S, M].

    vals_hot : [U, Q, L] bool multi-hot over (key,value) vocab (In/NotIn)
    key_hot  : [U, Q, K] bool multi-hot over key vocab (Exists/DoesNotExist)
    negate   : [U, Q] bool    requirement result is inverted
    use_key  : [U, Q] bool    requirement tests key presence, not values
    req_valid: [U, Q] bool    padding mask for requirements
    num_key  : [U, Q] i32     key index for Gt/Lt (0 if unused)
    num_op   : [U, Q] i32     0 = none, 1 = Gt, 2 = Lt
    num_val  : [U, Q] f32     comparison constant for Gt/Lt
    sel_valid: [U] bool       nil/padding selectors (match nothing)
    index    : [S] i32        slot -> unique row
    """
    vals_hot: jnp.ndarray
    key_hot: jnp.ndarray
    negate: jnp.ndarray
    use_key: jnp.ndarray
    req_valid: jnp.ndarray
    num_key: jnp.ndarray
    num_op: jnp.ndarray
    num_val: jnp.ndarray
    sel_valid: jnp.ndarray
    index: jnp.ndarray

    @property
    def n_selectors(self) -> int:
        return self.index.shape[0]


def match_selectors(sel: SelectorSet,
                    kv: jnp.ndarray,      # [M, L] bool/float — target has (key,value)
                    key: jnp.ndarray,     # [M, K] bool/float — target has key
                    num: Optional[jnp.ndarray] = None,  # [M, K] f32 numeric label values (+inf = non-numeric)
                    ) -> jnp.ndarray:
    """Match S selector slots against M targets -> [S, M] bool.

    The two einsums are batched matmuls over the U unique selectors;
    per-slot results are a gather on the slot index.
    """
    return jnp.take(match_selectors_unique(sel, kv, key, num), sel.index,
                    axis=0)


def match_selectors_unique(sel: SelectorSet,
                           kv: jnp.ndarray,
                           key: jnp.ndarray,
                           num: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The [U, M] unique-selector match matrix behind match_selectors;
    slot s maps to row sel.index[s].  Consumers that aggregate per unique
    selector (e.g. gang's intra-round deferral) use this directly to stay
    O(U x M) instead of O(S x M)."""
    kv_f = kv.astype(jnp.float32)
    key_f = key.astype(jnp.float32)
    cnt_v = jnp.einsum("uql,ml->uqm", sel.vals_hot.astype(jnp.float32), kv_f,
                       preferred_element_type=jnp.float32)
    cnt_k = jnp.einsum("uqk,mk->uqm", sel.key_hot.astype(jnp.float32), key_f,
                       preferred_element_type=jnp.float32)
    present = jnp.where(sel.use_key[..., None], cnt_k > 0.5, cnt_v > 0.5)
    ok = present ^ sel.negate[..., None]

    if num is not None:
        # Gt/Lt: gather each requirement's numeric label column.
        nval = jnp.take(num.T, jnp.clip(sel.num_key, 0, num.shape[1] - 1),
                        axis=0)  # [U, Q, M]
        is_gt = sel.num_op[..., None] == 1
        cmp = jnp.where(is_gt, nval > sel.num_val[..., None],
                        nval < sel.num_val[..., None])
        # absent/non-numeric labels are +inf (NaN-free cluster contract,
        # state/tensors.py): isfinite fails them for both Gt and Lt
        cmp = jnp.logical_and(cmp, jnp.isfinite(nval))
        ok = jnp.where(sel.num_op[..., None] > 0, cmp, ok)

    ok = jnp.logical_or(ok, jnp.logical_not(sel.req_valid[..., None]))
    return jnp.logical_and(jnp.all(ok, axis=1), sel.sel_valid[:, None])


def pad_selector_slots(s: SelectorSet, to: int) -> SelectorSet:
    """Pad the SLOT axis to `to` entries (index 0, callers mask via their
    own validity arrays — every consumer ANDs a valid mask over slots)."""
    idx = jnp.asarray(s.index)
    n = to - idx.shape[0]
    if n <= 0:
        return s
    return s._replace(index=jnp.concatenate(
        [idx, jnp.zeros((n,), idx.dtype)]))


def concat_selector_sets(a: SelectorSet, b: SelectorSet) -> SelectorSet:
    """Concatenate two SelectorSets compiled against the SAME vocab (same
    InternTable): unique rows are stacked (b's slot indices shifted), and the
    requirement axis is padded to the larger Q.  Works on traced arrays, so
    gang mode can splice batch-pod terms into the snapshot's ExistingTerms
    inside jit."""
    qa, qb = a.req_valid.shape[1], b.req_valid.shape[1]
    q = max(qa, qb)

    def padq(x, have):
        if have == q:
            return x
        pad = [(0, 0)] * x.ndim
        pad[1] = (0, q - have)
        return jnp.pad(x, pad)

    ua = a.sel_valid.shape[0]
    return SelectorSet(
        vals_hot=jnp.concatenate([padq(a.vals_hot, qa), padq(b.vals_hot, qb)]),
        key_hot=jnp.concatenate([padq(a.key_hot, qa), padq(b.key_hot, qb)]),
        negate=jnp.concatenate([padq(a.negate, qa), padq(b.negate, qb)]),
        use_key=jnp.concatenate([padq(a.use_key, qa), padq(b.use_key, qb)]),
        req_valid=jnp.concatenate([padq(a.req_valid, qa),
                                   padq(b.req_valid, qb)]),
        num_key=jnp.concatenate([padq(a.num_key, qa), padq(b.num_key, qb)]),
        num_op=jnp.concatenate([padq(a.num_op, qa), padq(b.num_op, qb)]),
        num_val=jnp.concatenate([padq(a.num_val, qa), padq(b.num_val, qb)]),
        sel_valid=jnp.concatenate([a.sel_valid, b.sel_valid]),
        index=jnp.concatenate([jnp.asarray(a.index),
                               jnp.asarray(b.index) + ua]),
    )


# ---------------------------------------------------------------------------
# host-side compiler


SelectorLike = Union[api.LabelSelector, api.NodeSelectorTerm, dict, None]

# Synthetic label-key prefix for NodeSelectorTerm.match_fields (the only
# supported field is metadata.name, reference:
# pkg/apis/core/v1/helper/helpers.go GetNodeFieldSelectorMap).
FIELD_PREFIX = "__field__"


class _Req(NamedTuple):
    op: str
    key: str
    values: Sequence[str]


def _reqs_of(sel: SelectorLike) -> Optional[List[_Req]]:
    """Normalize any selector-ish object to a requirement list; None => the
    selector matches nothing (nil selector)."""
    if sel is None:
        return None
    if isinstance(sel, dict):  # plain match-labels map (e.g. spec.nodeSelector)
        return [_Req("In", k, [v]) for k, v in sorted(sel.items())]
    if isinstance(sel, api.LabelSelector):
        return [_Req(r.operator, r.key, list(r.values)) for r in sel.requirements()]
    if isinstance(sel, api.NodeSelectorTerm):
        reqs = [_Req(r.operator, r.key, list(r.values)) for r in sel.match_expressions]
        reqs += [_Req(r.operator, FIELD_PREFIX + r.key, list(r.values))
                 for r in sel.match_fields]
        # A term with no expressions and no fields matches nothing
        # (reference: pkg/apis/core/v1/helper/helpers.go:180 MatchNodeSelectorTerms).
        if not reqs:
            return None
        return reqs
    raise TypeError(f"unsupported selector type {type(sel)}")


def selector_key(sel: SelectorLike) -> Optional[tuple]:
    """A selector's requirement content as a hashable key, ``(op, key,
    values)`` a requirement; None for a nil selector.  Selectors with
    equal keys compile to ONE unique row (``SelectorCompiler.compile``),
    and a table kept by row finds a selector it has seen by this key
    (state/tensors.py TermTable)."""
    reqs = _reqs_of(sel)
    if reqs is None:
        return None
    return tuple((r.op, r.key, tuple(r.values)) for r in reqs)


def empty_unique_rows(U: int, Q: int, L: int, K: int) -> dict:
    """The nine unique-selector leaves of a SelectorSet, all padding."""
    return dict(vals_hot=np.zeros((U, Q, L), bool),
                key_hot=np.zeros((U, Q, K), bool),
                negate=np.zeros((U, Q), bool),
                use_key=np.zeros((U, Q), bool),
                req_valid=np.zeros((U, Q), bool),
                num_key=np.zeros((U, Q), np.int32),
                num_op=np.zeros((U, Q), np.int32),
                num_val=np.zeros((U, Q), np.float32),
                sel_valid=np.zeros((U,), bool))


class SelectorCompiler:
    """Compiles host selector objects into a SelectorSet of numpy arrays."""

    def __init__(self, table: InternTable):
        self.table = table

    def compile(self, selectors: Sequence[SelectorLike],
                pad_s: Optional[int] = None,
                intern_new: bool = True,
                keys_out: Optional[dict] = None) -> SelectorSet:
        """intern_new: selectors may introduce vocab entries (normally the
        snapshot builder has already interned all cluster labels; pod
        selectors referencing unknown values simply never match, so lookups
        use get() when intern_new=False).

        Identical requirement lists compile to ONE unique row shared via the
        slot index — both the numpy build work and the device tensors scale
        with the number of distinct selectors, not the batch size.

        keys_out: an empty dict that takes ``selector_key -> unique row``
        for every unique row compiled (the nil selector's under None)."""
        keys = [selector_key(s) for s in selectors]
        S = pad_s if pad_s is not None else pow2_bucket(len(selectors), 1)
        if S < len(selectors):
            raise ValueError("pad_s smaller than selector count")

        uniq: dict = {} if keys_out is None else keys_out
        index = np.zeros((S,), np.int32)
        for i in range(S):
            k = keys[i] if i < len(keys) else None
            u = uniq.get(k, -1)
            if u < 0:
                u = uniq[k] = len(uniq)
            index[i] = u

        max_q = max((len(k) for k in uniq if k), default=1)
        sel = SelectorSet(index=index, **empty_unique_rows(
            pow2_bucket(len(uniq), 1), pow2_bucket(max_q, 2),
            self.table.kv.cap, self.table.key.cap))
        for k, u in uniq.items():
            self.fill_unique(sel, u, k, intern_new=intern_new)
        return sel

    def fill_unique(self, sel: SelectorSet, i: int, key: Optional[tuple],
                    intern_new: bool = True) -> None:
        """Write unique row ``i`` (all padding so far) of ``sel``'s numpy
        leaves from a ``selector_key``.  An id past the leaves' width is
        left out: it was interned here, across a cap, so no label that any
        pod or node carries reads it yet, and the owner of the leaves
        rebuilds them at the new width before one does (the
        DeltaTensorizer's vocab-growth resync)."""
        if key is None:
            return  # matches nothing
        kv_id = (self.table.kv.intern if intern_new else self.table.kv.get)
        key_id = (self.table.key.intern if intern_new else self.table.key.get)
        L, K = sel.vals_hot.shape[2], sel.key_hot.shape[2]
        sel.sel_valid[i] = True
        for q, (op, rkey, values) in enumerate(key):
            sel.req_valid[i, q] = True
            if op in ("In", "NotIn"):
                for v in values:
                    j = kv_id((rkey, v))
                    if 0 <= j < L:
                        sel.vals_hot[i, q, j] = True
                sel.negate[i, q] = (op == "NotIn")
            elif op in ("Exists", "DoesNotExist"):
                j = key_id(rkey)
                if 0 <= j < K:
                    sel.key_hot[i, q, j] = True
                sel.use_key[i, q] = True
                sel.negate[i, q] = (op == "DoesNotExist")
            elif op in ("Gt", "Lt"):
                j = key_id(rkey)
                sel.num_key[i, q] = max(j, 0)
                sel.num_op[i, q] = 1 if op == "Gt" else 2
                try:
                    sel.num_val[i, q] = float(int(values[0]))
                except (ValueError, IndexError):
                    # unparsable constant never matches: impossible compare
                    sel.num_op[i, q] = 1
                    sel.num_val[i, q] = np.inf
                if j < 0:
                    # unknown key can never be numeric-matched
                    sel.num_val[i, q] = np.inf if op == "Gt" else -np.inf
            else:
                raise ValueError(f"unknown selector op {op}")
