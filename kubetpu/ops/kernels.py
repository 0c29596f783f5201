"""Batched Filter/Score kernels: the TPU-native re-implementation of every
default-enabled scheduler plugin's algorithm (reference:
pkg/scheduler/framework/plugins/*, default matrix in
pkg/scheduler/algorithmprovider/registry.go:77-160).

Shape conventions: B pending pods x N nodes x P existing pods.  All kernels
are pure jnp functions over (ClusterTensors, PodBatch) pytrees, composed and
jitted by kubetpu/models/programs.py.  Where the reference runs int64
arithmetic, we use f32 with explicit floor() at every integer-division /
truncation site so scores agree exactly for in-range values (see
state/tensors.py for the unit-scaling argument).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..state.tensors import CH_CPU, CH_EPH, CH_MEM, CH_PODS, N_FIXED_CHANNELS
from .selectors import match_selectors, match_selectors_unique

MAX_NODE_SCORE = 100.0  # reference: framework/v1alpha1/interface.go:85


def _f(x):
    return x.astype(jnp.float32)


def _idiv(a, b):
    """Go int64 division (truncation toward zero) for non-negative operands;
    b == 0 guarded by callers.

    floor(a / b) alone is WRONG under XLA on TPU: fast-math lowers x/b to
    x * (1/b), and e.g. 200 * (1/100) = 1.9999999 floors to 1.  Both
    operands here are exact integers in f32 range, so one remainder
    correction recovers the exact quotient."""
    q = jnp.floor(a / b)  # kubelint: ignore[numeric/floor-div] this IS the corrected division — the remainder fixup below recovers the exact quotient
    r = a - q * b
    return q + jnp.where(r >= b, 1.0, 0.0) - jnp.where(r < 0, 1.0, 0.0)


# ---------------------------------------------------------------------------
# blessed exact cross-axis reductions
#
# The only sanctioned ways to reduce across shard_map mesh axes (kubelint
# exact/raw-collective-reduce + exact/raw-tie-argmax route every call
# site here; tools/kubeexact proves the discipline on the traced jaxprs).
# The contract:
#
#   * float max/min are exactly associative — any tile order, same bits;
#   * float sums must be integer-valued with |value| < 2**24 (callers are
#     responsible; kubeexact checks the bound at north-star shapes);
#   * tie-broken argmax must decompose through the per-pod gumbel plane
#     (argmax over where(tie, gumbel, neg) == jax.random.categorical over
#     the tie set) and cross-axis selection must fold (best, gumbel,
#     lowest-index) by STRICT improvement so the winner equals the
#     replicated jnp.argmax bit-for-bit.
#
# The sentinel (neg) rides in from the caller as jnp.float32(-2**62): its
# strong type is part of the program's committed lowering.


def exact_psum(x, axis_name):
    """Cross-shard sum under the integer-exactness contract (int dtypes,
    or integer-valued f32 with range < 2**24 — see tools/kubeexact)."""
    return jax.lax.psum(x, axis_name)


def exact_pmax(x, axis_name):
    """Cross-shard float/int max: exactly associative, always bit-stable."""
    return jax.lax.pmax(x, axis_name)


def exact_pmin(x, axis_name):
    """Cross-shard float/int min: exactly associative, always bit-stable."""
    return jax.lax.pmin(x, axis_name)


def gumbel_tiebreak_argmax(total, f, gumbel, col_offset, neg):
    """Per-tile propose half of the selectHost decomposition.

    Masks infeasible columns to ``neg``, takes the tile max, then breaks
    exact score ties by gumbel (argmax over where(tie, gumbel, neg) is
    jax.random.categorical restricted to the tie set — selectHost's
    reservoir draw).  Returns (tile_best, tile_h, tile_arg) with
    tile_arg offset into global column space by ``col_offset``;
    jnp.argmax keeps the lowest index on exact gumbel ties, which is the
    first-index contract the cross-axis fold preserves."""
    masked = jnp.where(f, total, neg)
    tile_best = jnp.max(masked, axis=1, keepdims=True)
    h = jnp.where((masked == tile_best) & f, gumbel, neg)
    tile_h = jnp.max(h, axis=1, keepdims=True)
    tile_arg = (jnp.argmax(h, axis=1, keepdims=True).astype(jnp.int32)
                + col_offset)
    return tile_best[:, 0], tile_h[:, 0], tile_arg[:, 0]


def crossaxis_first_index_argmax(tile_best, tile_h, tile_arg, axis_name,
                                 neg):
    """Cross-shard resolve half: max score, then max gumbel among score
    ties, then MIN global index among exact (score, gumbel) ties — all
    via exactly-associative pmax/pmin, so the winner is the index the
    replicated jnp.argmax would have chosen (gather-free)."""
    best = jax.lax.pmax(tile_best, axis_name)
    gh = jax.lax.pmax(jnp.where(tile_best == best, tile_h, neg),
                      axis_name)
    cand = jnp.where((tile_best == best) & (tile_h == gh), tile_arg,
                     jnp.int32(2 ** 30))
    return best, jax.lax.pmin(cand, axis_name)


# ---------------------------------------------------------------------------
# shared aggregation helpers


def _if_live(live, live_fn, dead_fn):
    """The runtime gate of one of a batch's term sets (required affinity,
    required anti-affinity, preferred terms, hard and soft spread
    constraints, the controller selectors).

    Everything a topology kernel does along the existing-pod axis for one
    set — the [., P] selector match and its [., P] x [P, N] contractions —
    is live_fn; dead_fn is what that code returns when no row of the set
    is valid, built from shapes alone.  ``live`` is a device bool scalar
    read from the BATCH's own arrays, never the cluster's, so one compiled
    program does the work each batch needs and a vmap over clusters
    (preemption's candidates) leaves the cond a cond.  Both branches must
    return the same node-shaped ([., N]) or row-shaped avals."""
    return jax.lax.cond(live, live_fn, dead_fn)


def _pod_axis_match(cluster, sel) -> jnp.ndarray:
    """[U, P] match of a SelectorSet's UNIQUE selectors against the
    existing pods — the assignment-independent part of every topology
    kernel's pod-axis work."""
    return match_selectors_unique(sel, cluster.pod_kv, cluster.pod_key)


def _pod_axis_pre(cluster, sel, live) -> jnp.ndarray:
    """_pod_axis_match as gang mode hoists it out of its rounds, behind
    the set's gate: all False, unmatched, for a dead set (the kernels never
    read it).  Rows expand to the batch's slots ([B, T, P], and meet the
    namespaces) only inside the kernel's own gate, so nothing
    [B, T, P]-sized outlives a round, or exists at all for a dead set."""
    U = sel.sel_valid.shape[0]
    P = cluster.pod_valid.shape[0]
    return _if_live(live, lambda: _pod_axis_match(cluster, sel),
                    lambda: jnp.zeros((U, P), bool))


def per_node_counts(match_sp: jnp.ndarray, pod_node: jnp.ndarray, n_nodes: int) -> jnp.ndarray:
    """[S, P] per-existing-pod values -> [S, N] per-node sums.

    One-hot MATMUL, not a scatter: TPU scatters serialize, while a
    [S, P] x [P, N] contraction rides the MXU.  bf16 inputs are exact for
    the bool/small-int values every caller passes (products are exact and
    the MXU accumulates in f32), so counts are bit-exact up to 2^24."""
    oh = (pod_node[:, None] == jnp.arange(n_nodes)[None, :])  # [P, N]
    return jnp.einsum("sp,pn->sn", match_sp.astype(jnp.bfloat16),
                      oh.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _samepair_pods_to_nodes(cluster, values_sp: jnp.ndarray,
                            keys_s: jnp.ndarray, pod_node: jnp.ndarray,
                            pod_valid: jnp.ndarray,
                            active_keys=None) -> jnp.ndarray:
    """out[s, n] = sum of values[s, p] over existing pods p placed on a node
    sharing node n's (keys_s[s], value) topology pair.

    This is the MXU form of scatter-to-pair-space + gather-back-to-nodes
    (pair_scatter/pair_gather): one [S, P] x [P, N] matmul per topology key
    (TK static, unrolled), with the same-pair membership matrix built
    elementwise.  Rows whose key id is out of [0, TK) yield zeros; nodes
    without the key receive 0; pods on nodes without the key contribute
    nothing.  values must be bf16-exact per element (bools or small ints —
    accumulation is f32 on the MXU, so sums are exact).

    active_keys: optional static iterable of the topology-key ids that can
    appear in keys_s — the matmul runs ONLY for those keys (typical
    workloads touch 2 of the TK=8 seeded keys, a 4x FLOP cut).  MUST be a
    superset of every key in keys_s or those rows silently read 0; None
    means all keys."""
    tp = cluster.topo_pair                      # [N, TK]
    TK = tp.shape[1]
    pod_tp = jnp.take(tp, jnp.clip(pod_node, 0, None), axis=0)  # [P, TK]
    placed = (pod_node >= 0) & pod_valid
    vals = values_sp.astype(jnp.bfloat16)
    out = jnp.zeros((values_sp.shape[0], tp.shape[0]), jnp.float32)
    keys = range(TK) if active_keys is None else \
        [k for k in active_keys if 0 <= k < TK]
    for k in keys:
        pk = jnp.where(placed, pod_tp[:, k], -1)            # [P]
        sp = (pk[:, None] == tp[None, :, k]) & (pk >= 0)[:, None]
        red = jnp.einsum("sp,pn->sn", vals, sp.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        out = jnp.where((keys_s == k)[:, None], red, out)
    return out


def _samepair_nodes(cluster, values_sn: jnp.ndarray,
                    keys_s: jnp.ndarray, active_keys=None) -> jnp.ndarray:
    """out[s, n] = sum of values[s, n'] over nodes n' sharing node n's
    (keys_s[s], value) pair — the node-valued sibling of
    _samepair_pods_to_nodes ([S, N] x [N, N] matmul per key; same
    active_keys contract)."""
    tp = cluster.topo_pair
    TK = tp.shape[1]
    vals = values_sn.astype(jnp.bfloat16)
    out = jnp.zeros(values_sn.shape, jnp.float32)
    keys = range(TK) if active_keys is None else \
        [k for k in active_keys if 0 <= k < TK]
    for k in keys:
        col = tp[:, k]
        sp = (col[:, None] == col[None, :]) & (col >= 0)[:, None]
        red = jnp.einsum("sn,nm->sm", vals, sp.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)
        out = jnp.where((keys_s == k)[:, None], red, out)
    return out


def pair_scatter(values_sn: jnp.ndarray, pair_sn: jnp.ndarray, L: int) -> jnp.ndarray:
    """Aggregate per-(s, item) values by topology-pair id -> [S, L].
    pair id -1 entries are dropped."""
    ids = jnp.where(pair_sn >= 0, pair_sn, L)
    out = jax.vmap(lambda v, i: jax.ops.segment_sum(v, i, num_segments=L + 1))(
        _f(values_sn), ids)
    return out[:, :L]


def pair_gather(pair_counts_sl: jnp.ndarray, pair_sn: jnp.ndarray) -> jnp.ndarray:
    """[S, L] pair values gathered back to items via [S, N] pair ids; -1 -> 0."""
    got = jnp.take_along_axis(pair_counts_sl, jnp.clip(pair_sn, 0, None), axis=1)
    return jnp.where(pair_sn >= 0, got, 0.0)


def node_topo_pairs(cluster, topo_key_sb: jnp.ndarray) -> jnp.ndarray:
    """For selector rows with topology-key ids [S] (or [S, ...] flattened),
    return each node's pair id [S, N] (-1 if the node lacks the key)."""
    return jnp.take(cluster.topo_pair.T, topo_key_sb, axis=0)  # [S, N]


def pod_topo_pairs(cluster, topo_key_s: jnp.ndarray) -> jnp.ndarray:
    """Pair ids of each *existing pod's node* for given keys -> [S, P]."""
    pod_topo = jnp.take(cluster.topo_pair, jnp.clip(cluster.pod_node, 0, None),
                        axis=0)  # [P, TK]
    pairs = jnp.take(pod_topo.T, topo_key_s, axis=0)  # [S, P]
    return jnp.where((cluster.pod_node >= 0) & cluster.pod_valid, pairs, -1)


# ---------------------------------------------------------------------------
# filters — each returns ok [B, N] bool (over valid nodes; caller masks padding)


def fit_rows(req: jnp.ndarray, avail: jnp.ndarray) -> jnp.ndarray:
    """Row-wise NodeResourcesFit verdict: request rows [X, R] against
    available rows [X, R] (fit.go:194-267 semantics: pod count always
    checked; cpu/mem/ephemeral checked when the pod requests anything;
    scalar channels only when requested)."""
    free_ok = avail >= req
    R = req.shape[-1]
    # channel masks broadcast EXPLICITLY against the [..., R] operands:
    # bare [R] | [X, R] is an implicit rank promotion the sanitizer
    # (KUBETPU_SANITIZE rank_promotion="raise") rejects
    shape1 = (1,) * (req.ndim - 1) + (R,)
    ch = jnp.arange(R).reshape(shape1)
    is_fixed = (ch < N_FIXED_CHANNELS) & (ch != CH_PODS)
    is_pods = ch == CH_PODS
    check = jnp.where(is_fixed, True, req > 0)
    res_ok = jnp.all(free_ok | ~check | is_pods, axis=-1)
    pods_ok = free_ok[..., CH_PODS]
    nonpods = jnp.where(is_pods, 0.0, req)
    zero_req = jnp.all(nonpods == 0, axis=-1)
    return pods_ok & (zero_req | res_ok)


def fit_filter(cluster, batch, ignored_channels: jnp.ndarray | None = None) -> jnp.ndarray:
    """NodeResourcesFit (reference: noderesources/fit.go:194-267 fitsRequest).
    ignored_channels: optional [R] f32 mask, 1.0 = check the channel."""
    alloc, used, req = cluster.allocatable, cluster.requested, batch.req
    free_ok = alloc[None, :, :] >= req[:, None, :] + used[None, :, :]  # [B, N, R]
    R = alloc.shape[1]
    ch = jnp.arange(R)[None, None, :]  # explicit [1, 1, R] broadcast
    # pod count is always checked; cpu/mem/ephemeral checked whenever the pod
    # requests anything at all; scalar channels only when requested.
    is_fixed = (ch < N_FIXED_CHANNELS) & (ch != CH_PODS)
    is_pods = ch == CH_PODS
    scalar_req = req[:, None, :] > 0
    check = jnp.where(is_fixed, True, scalar_req)
    if ignored_channels is not None:
        check = jnp.logical_and(check, (ignored_channels > 0)[None, None, :])
    res_ok = jnp.all(free_ok | ~check | is_pods, axis=-1)
    pods_ok = free_ok[:, :, CH_PODS]
    nonpods = jnp.where(is_pods[0], 0.0, req)
    zero_req = jnp.all(nonpods == 0, axis=-1)  # [B]
    return pods_ok & (zero_req[:, None] | res_ok)


def node_name_filter(cluster, batch) -> jnp.ndarray:
    """NodeName (reference: nodename/node_name.go:51)."""
    has = jnp.take(cluster.kv.T, jnp.clip(batch.node_name_kvid, 0, None), axis=0)
    named_ok = has & (batch.node_name_kvid >= 0)[:, None]
    return jnp.where(batch.has_node_name[:, None], named_ok, True)


def node_unschedulable_filter(cluster, batch) -> jnp.ndarray:
    """NodeUnschedulable (reference: nodeunschedulable/node_unschedulable.go:51)."""
    return ~(cluster.unschedulable[None, :]
             & ~batch.tolerates_unschedulable[:, None])


def node_ports_filter(cluster, batch) -> jnp.ndarray:
    """NodePorts (reference: nodeports/node_ports.go:108; wildcard semantics
    encoded at intern time, see state/tensors.py port_ids)."""
    conflicts = jnp.einsum("bp,np->bn", batch.ports_hot, _f(cluster.ports),
                           preferred_element_type=jnp.float32)
    return conflicts < 0.5


def taint_filter(cluster, batch) -> jnp.ndarray:
    """TaintToleration: untolerated NoSchedule/NoExecute taint fails
    (reference: tainttoleration/taint_toleration.go:54-72)."""
    untol_hard = _f(~batch.tolerated) * _f(cluster.taint_is_hard)[None, :]
    hits = jnp.einsum("bt,nt->bn", untol_hard, _f(cluster.taints),
                      preferred_element_type=jnp.float32)
    return hits < 0.5


def node_affinity_filter(cluster, batch) -> jnp.ndarray:
    """NodeAffinity + spec.nodeSelector (reference:
    nodeaffinity/node_affinity.go:54, plugins/helper/node_affinity.go
    PodMatchesNodeSelectorAndAffinityTerms).  Also reused by the topology
    spread kernels as the node-eligibility mask."""
    B = batch.req.shape[0]
    sel_ok = match_selectors(batch.node_selector, cluster.kv, cluster.keymask,
                             cluster.num)  # [B, N]
    term_m = match_selectors(batch.rna_sel, cluster.kv, cluster.keymask,
                             cluster.num)  # [B*Tn, N]
    Tn = batch.rna_valid.shape[1]
    term_m = term_m.reshape(B, Tn, -1)
    any_term = jnp.any(term_m & batch.rna_valid[:, :, None], axis=1)
    rna_ok = jnp.where(batch.has_rna[:, None], any_term, True)
    return sel_ok & rna_ok


# ---------------------------------------------------------------------------
# PodTopologySpread's raw score: int64(sum of float64(count) * log(size + 2))
#
# The reference multiplies in float64 and truncates.  A float32 product
# floors to another integer wherever cnt * log(size + 2) lies within the
# float32 error of one: with the host's float32 log at 0.3% of the pairs
# (cnt to 16,384, size to 8,192), for three zones first at 4,217 matching
# pods; with a v5e's own log, good to five digits (log 5 reads 1.60946),
# at 16% of them, for three zones first at 233 (PERF.md, section 6, PR 42;
# tests/test_spread_soft_product.py has the enumeration).  So the product
# is made in integers: log(k + 2) as the float64 the reference holds,
# taken from the HOST's math.log at trace time and carried as a fixed-point
# integer in 12-bit limbs, the count in two, schoolbook products in int32,
# the floor a shift.  That is the floor of the EXACT product of the count
# and the float64 weight, summed over a pod's constraints before the
# floor as the reference sums before it truncates; the reference's own
# float64 rounding of each product and of their sum (relative 2**-52)
# could part from it only where the exact sum lies that close under an
# integer, which no enumerated pair does.

_LIMB_BITS = 12
_LIMB_MASK = (1 << _LIMB_BITS) - 1
_LOG_LIMBS = 5            # 60 bits: log(k + 2) * 2**53 < 2**57 up to k = 8.8e6
_LOG_FRAC_BITS = 53       # log(k + 2) >= log 2 > 1/2: a float64 there is a
                          # multiple of 2**-53
_CNT_LIMBS = 2            # counts < 2**24 (per_node_counts' own bound)
_MAX_SOFT_CONSTRAINTS = 32   # C * 2 * 2**24 stays inside int32


@functools.lru_cache(maxsize=None)
def _log_weight_limbs(n: int) -> np.ndarray:
    """[n, _LOG_LIMBS] int32: math.log(k + 2) * 2**53 for k in 0..n-1,
    exactly (a float64 is a dyadic rational), least limb first."""
    out = np.zeros((n, _LOG_LIMBS), np.int32)
    for k in range(n):
        num, den = math.log(k + 2.0).as_integer_ratio()
        fixed = (num << _LOG_FRAC_BITS) // den       # den divides 2**53
        for i in range(_LOG_LIMBS):
            out[k, i] = (fixed >> (_LIMB_BITS * i)) & _LIMB_MASK
    return out


def log_weighted_floor(cnt: jnp.ndarray, size: jnp.ndarray,
                       counted: jnp.ndarray, n_sizes: int) -> jnp.ndarray:
    """floor(sum over constraints c of cnt[..., c, n] * log(size[..., c] + 2))
    over the ``counted`` entries, per node: [..., C, N] f32 whole-number
    counts under 2**24, [..., C] f32 whole-number sizes in [0, n_sizes),
    [..., C, N] bool -> [..., N] f32, a whole number that is exact while
    the score stays under 2**24 (NormalizeScore's own f32 bound is lower:
    100 x twice the score).  See the note above."""
    if cnt.shape[-2] > _MAX_SOFT_CONSTRAINTS:
        raise ValueError(f"{cnt.shape[-2]} soft constraints a pod: the limb "
                         f"sums hold {_MAX_SOFT_CONSTRAINTS}")
    table = jnp.asarray(_log_weight_limbs(n_sizes))
    w = jnp.take(table, jnp.clip(size.astype(jnp.int32), 0, n_sizes - 1),
                 axis=0)                                    # [..., C, 5]
    ci = jnp.where(counted, cnt, 0.0).astype(jnp.int32)
    c_limbs = (ci & _LIMB_MASK, ci >> _LIMB_BITS)
    limbs = [jnp.zeros(cnt.shape[:-2] + cnt.shape[-1:], jnp.int32)
             for _ in range(_LOG_LIMBS + _CNT_LIMBS)]
    for j, c in enumerate(c_limbs):
        for i in range(_LOG_LIMBS):
            limbs[i + j] = limbs[i + j] + jnp.sum(
                c * w[..., i][..., None], axis=-2)
    for k in range(len(limbs) - 1):                  # carry, least first
        limbs[k + 1] = limbs[k + 1] + (limbs[k] >> _LIMB_BITS)
        limbs[k] = limbs[k] & _LIMB_MASK
    whole, part = divmod(_LOG_FRAC_BITS, _LIMB_BITS)  # bits below the point
    out = limbs[whole] >> part
    for k in range(whole + 1, len(limbs)):
        out = out + (limbs[k] << (_LIMB_BITS * (k - whole) - part))
    return out.astype(jnp.float32)


# ---------------------------------------------------------------------------
# PodTopologySpread


class SpreadState(NamedTuple):
    node_counts: jnp.ndarray   # [B, C, N] matching-pod counts per node
    pair_counts: jnp.ndarray   # [B*C, L] counts per registered pair
    registered: jnp.ndarray    # [B*C, L] bool pair registered from eligible nodes
    node_pair: jnp.ndarray     # [B*C, N] node's pair id per constraint
    has_key: jnp.ndarray       # [B, C, N] node has the topology key
    eligible: jnp.ndarray      # [B, N] affinity-ok nodes with all constraint keys
    any_eligible: jnp.ndarray  # [B]


def _spread_match_ns(cluster, batch, constraints, pre=None) -> jnp.ndarray:
    """[B, C, P] constraint-selector x namespace match against the pod axis
    — the assignment-independent part of _spread_state.  pre: the
    constraints' unique-selector match [Us, P] (spread_match_ns)."""
    B, C = constraints.topo_key.shape
    if pre is None:
        pre = _pod_axis_match(cluster, constraints.sel)
    m = jnp.take(pre, constraints.sel.index, axis=0)  # [B*C, P]
    ns_ok = jnp.einsum("bn,pn->bp", batch.ns_hot, cluster.pod_ns_hot,
                       preferred_element_type=jnp.float32) > 0.5
    return m.reshape(B, C, -1) & ns_ok[:, None, :]


def spread_match_ns(cluster, batch, constraints) -> jnp.ndarray:
    """The hoisted pre of a constraint set ([Us, P], _pod_axis_pre)."""
    return _pod_axis_pre(cluster, constraints.sel,
                         jnp.any(constraints.valid))


def _spread_state(cluster, batch, constraints, affinity_ok, count_mask_nodes,
                  match_ns=None) -> SpreadState:
    """Shared machinery of hard-filter and soft-score spreading.

    constraints: batch.spread or batch.spread_soft.
    count_mask_nodes: [B, N] bool — nodes whose pods are counted into pair
    sums (PreFilter counts every node's pods into registered pairs; PreScore
    counts only affinity-matching nodes with all keys).
    match_ns: optional precomputed spread_match_ns output."""
    B, C = constraints.topo_key.shape
    N = cluster.allocatable.shape[0]
    L = cluster.kv.shape[1]

    # matching existing pods: same namespace, selector, non-terminating
    # (reference: podtopologyspread/common.go:87 countPodsMatchSelector)
    if match_ns is None:
        match_ns = _spread_match_ns(cluster, batch, constraints)
    countable = cluster.pod_valid & ~cluster.pod_terminating
    m = match_ns & countable[None, None, :]
    node_counts = per_node_counts(m.reshape(B * C, -1), cluster.pod_node,
                                  N).reshape(B, C, N)

    node_pair = node_topo_pairs(cluster, constraints.topo_key.reshape(-1))  # [B*C, N]
    has_key = ((node_pair >= 0).reshape(B, C, N)
               & constraints.topo_known.reshape(B, C)[:, :, None])
    node_pair = jnp.where(has_key.reshape(B * C, N), node_pair, -1)
    valid_c = constraints.valid  # [B, C]
    all_keys = jnp.all(has_key | ~valid_c[:, :, None], axis=1)  # [B, N]
    eligible = affinity_ok & cluster.node_valid[None, :] & all_keys
    any_eligible = jnp.any(eligible, axis=1)

    elig_bc = jnp.broadcast_to(eligible[:, None, :], (B, C, N)).reshape(B * C, N)
    registered = pair_scatter(elig_bc, node_pair, L) > 0.5
    counted = jnp.broadcast_to(count_mask_nodes[:, None, :], (B, C, N)).reshape(B * C, N)
    pair_counts = pair_scatter(node_counts.reshape(B * C, N) * _f(counted),
                               node_pair, L)
    pair_counts = jnp.where(registered, pair_counts, 0.0)
    return SpreadState(node_counts=node_counts, pair_counts=pair_counts,
                       registered=registered, node_pair=node_pair,
                       has_key=has_key, eligible=eligible,
                       any_eligible=any_eligible)


class SpreadRoom(NamedTuple):
    """What one evaluation of the hard spread filter hands the gang
    auction's round beside its verdict (spread_filter, return_slack): all
    from the filter's own intermediates, no second product."""
    slack: jnp.ndarray     # [B, C, N] f32 maxSkew - skew; +inf where the
                           # filter tolerates the pod whatever lands
    floor: jnp.ndarray     # [B, C, N] f32 matching pods in node n's pair
                           # where that pair is REGISTERED for (b, c),
                           # _UNREGISTERED elsewhere and on a row the
                           # filter does not check: its minimum over n is
                           # minMatch, and an unregistered pair is in none
    ok_wide: jnp.ndarray   # [B, N] bool the verdict with the skew test
                           # loosened by what the round can lift the
                           # minimum (open_pods); == ok where that is 0
    widened: jnp.ndarray   # [B] bool ok_wide passes a node ok fails


_UNREGISTERED = 2.0 ** 31   # above any count (per_node_counts: < 2**24)


def spread_filter(cluster, batch, affinity_ok, match_ns=None,
                  active_keys=None, return_slack: bool = False,
                  open_pods=None):
    """PodTopologySpread hard constraints
    (reference: podtopologyspread/filtering.go:200-283 calPreFilterState/Filter).

    Node-space formulation: pair aggregates are constant across a pair's
    member nodes, so "min over registered pairs" == "min over nodes of
    registered pairs" and no explicit pair axis is needed — everything is
    same-pair matmuls on the MXU (see _samepair_pods_to_nodes).

    return_slack=True returns (ok, SpreadRoom), the room from the same
    intermediates.  slack [B, C, N] = maxSkew - skew: how many MORE
    matching pods node n's pair can take before constraint c of pod b
    fails there against the minimum as it stands (>= 0 wherever c passes;
    the same value on every node of a pair).  floor [B, C, N]: the
    per-pair counts the minimum runs over, so a caller that adds pods to
    pairs can move the minimum with them.  ok_wide: the verdict with
    ``skew <= maxSkew + r`` for ``r = open_pods[b, c] // (registered
    pairs of (b, c))``, every other test (the key, eligibility, the empty
    preFilterState, cons.valid) as in ok: r is how far a round that
    admits open_pods more matching pods LEVEL over the registered pairs
    lifts the minimum, so a node ok_wide passes and ok fails is one whose
    pair can open inside such a round.  open_pods [B, C] i32 (None: 0,
    ok_wide == ok); three zones and 512 open pods give r = 170, a hostname
    constraint over 5,000 nodes r = 0.  The gang auction proposes over
    ok_wide and admits in pod order against floor and slack
    (models/gang.py)."""
    cons = batch.spread
    B, C = cons.topo_key.shape
    N = cluster.allocatable.shape[0]

    def live():
        m_ns = _spread_match_ns(cluster, batch, cons, pre=match_ns)
        countable = cluster.pod_valid & ~cluster.pod_terminating
        m = (m_ns & countable[None, None, :]).reshape(B * C, -1)
        keys = jnp.where(cons.topo_known, cons.topo_key, -1).reshape(-1)
        # matching-pod count of each node's pair, per constraint  [B*C, N]
        cnt = _samepair_pods_to_nodes(cluster, m, keys, cluster.pod_node,
                                      cluster.pod_valid,
                                      active_keys=active_keys)
        node_pair = node_topo_pairs(cluster, cons.topo_key.reshape(-1))
        has_key = ((node_pair >= 0).reshape(B, C, N)
                   & cons.topo_known.reshape(B, C)[:, :, None])
        all_keys = jnp.all(has_key | ~cons.valid[:, :, None], axis=1)  # [B, N]
        eligible = affinity_ok & cluster.node_valid[None, :] & all_keys
        any_eligible = jnp.any(eligible, axis=1)
        # a pair is registered iff some eligible node carries it
        elig_bc = jnp.broadcast_to(eligible[:, None, :],
                                   (B, C, N)).reshape(B * C, N)
        # eligible nodes of each node's pair  [B*C, N]
        pair_elig = _samepair_nodes(cluster, elig_bc, keys,
                                    active_keys=active_keys)
        registered = pair_elig > 0.5
        floor = jnp.where(registered, cnt, jnp.float32(_UNREGISTERED))
        min_match = jnp.min(floor, axis=1).reshape(B, C)
        # unregistered pair => matchNum 0 (reference Filter: nil *tpCount)
        match_num = jnp.where(registered, cnt, 0.0).reshape(B, C, N)
        self_m = _f(cons.self_match)[:, :, None]
        skew = match_num + self_m - min_match[:, :, None]
        c_ok = has_key & (skew <= cons.max_skew[:, :, None])
        ok = jnp.all(c_ok | ~cons.valid[:, :, None], axis=1)
        has_any = jnp.any(cons.valid, axis=1)
        # empty preFilterState (no eligible nodes anywhere) tolerates every pod
        ok = jnp.where(has_any[:, None] & any_eligible[:, None], ok, True)
        if not return_slack:
            return ok
        checked = has_any & any_eligible
        slack = jnp.where(checked[:, None, None],
                          cons.max_skew[:, :, None] - skew, jnp.inf)
        ok_wide = ok
        if open_pods is not None:
            # registered pairs of (b, c): each eligible node is 1 / (the
            # eligible nodes of its pair) of one; whole to well under 1/2
            n_pairs = jnp.round(jnp.sum(
                jnp.where(elig_bc & registered,
                          1.0 / jnp.maximum(pair_elig, 1.0), 0.0),
                axis=1)).astype(jnp.int32).reshape(B, C)
            lift = _f(open_pods // jnp.maximum(n_pairs, 1))
            c_wide = has_key & (skew <= (cons.max_skew + lift)[:, :, None])
            ok_wide = jnp.where(
                checked[:, None],
                jnp.all(c_wide | ~cons.valid[:, :, None], axis=1), True)
        # a row the filter does not check is in nobody's minimum, as in
        # the dead branch
        floor = jnp.where((checked[:, None] & cons.valid)[:, :, None],
                          floor.reshape(B, C, N),
                          jnp.float32(_UNREGISTERED))
        return ok, SpreadRoom(slack=slack, floor=floor, ok_wide=ok_wide,
                              widened=jnp.any(ok_wide & ~ok, axis=1))

    def dead():
        # no valid constraint on any pod: has_any is False on every row
        ok = jnp.ones((B, N), bool)
        if not return_slack:
            return ok
        return ok, SpreadRoom(
            slack=jnp.full((B, C, N), jnp.inf, jnp.float32),
            floor=jnp.full((B, C, N), _UNREGISTERED, jnp.float32),
            ok_wide=ok, widened=jnp.zeros((B,), bool))

    return _if_live(jnp.any(cons.valid), live, dead)


def spread_soft_score(cluster, batch, feasible, affinity_ok,
                      hostname_topokey: int, match_ns=None,
                      active_keys=None) -> jnp.ndarray:
    """PodTopologySpread soft constraints scoring, already normalized
    (reference: podtopologyspread/scoring.go PreScore/Score/NormalizeScore)."""
    cons = batch.spread_soft
    B, C = cons.topo_key.shape
    N = cluster.allocatable.shape[0]

    def live():
        count_nodes = affinity_ok & cluster.node_valid[None, :]
        m_ns = _spread_match_ns(cluster, batch, cons, pre=match_ns)
        countable = cluster.pod_valid & ~cluster.pod_terminating
        m = m_ns & countable[None, None, :]          # [B, C, P]
        keys = jnp.where(cons.topo_known, cons.topo_key, -1).reshape(-1)
        node_pair = node_topo_pairs(cluster, cons.topo_key.reshape(-1))
        has_key = ((node_pair >= 0).reshape(B, C, N)
                   & cons.topo_known.reshape(B, C)[:, :, None])
        is_host = (cons.topo_key == hostname_topokey) & cons.topo_known
        valid = cons.valid

        # per-node match counts (hostname constraints read these directly)
        node_counts = per_node_counts(m.reshape(B * C, -1), cluster.pod_node,
                                      N).reshape(B, C, N)
        # pair sums count only pods on PreScore-eligible nodes
        # (reference: scoring.go:139-165 counts over filtered+affinity nodes)
        cm_pods = jnp.take_along_axis(
            count_nodes, jnp.clip(cluster.pod_node, 0, None)[None, :], axis=1)
        cm_pods = cm_pods & (cluster.pod_node >= 0)[None, :]     # [B, P]
        m_counted = (m & cm_pods[:, None, :]).reshape(B * C, -1)
        cnt_pair = _samepair_pods_to_nodes(cluster, m_counted, keys,
                                           cluster.pod_node, cluster.pod_valid,
                                           active_keys=active_keys)

        # eligibility / registration from *filtered* nodes only
        all_keys = jnp.all(has_key | ~valid[:, :, None], axis=1)  # [B, N]
        ignored = feasible & ~all_keys
        scored = feasible & all_keys
        eligible = feasible & cluster.node_valid[None, :] & all_keys
        elig_bc = jnp.broadcast_to(eligible[:, None, :], (B, C, N)).reshape(B * C, N)
        members = _samepair_nodes(cluster, elig_bc, keys,
                                  active_keys=active_keys)      # [B*C, N]
        registered = members > 0.5

        # distinct registered-pair count: each pair contributes
        # sum-over-its-eligible-members of 1/members == exactly 1
        inv = jnp.where(registered & elig_bc, 1.0 / jnp.maximum(members, 1.0),
                        0.0)
        topo_size = jnp.round(jnp.sum(inv, axis=1)).reshape(B, C)
        n_scored = jnp.sum(_f(scored), axis=1)  # [B]
        size = jnp.where(is_host, n_scored[:, None], topo_size)

        pair_cnt = jnp.where(registered, cnt_pair, 0.0).reshape(B, C, N)
        cnt = jnp.where(is_host[:, :, None], node_counts, pair_cnt)
        # adjustForMaxSkew (scoring.go:294)
        ms = cons.max_skew[:, :, None]
        cnt = jnp.where(cnt < ms, ms - 1.0, cnt)
        # int64(sum of float64(cnt) * log(size + 2)) (scoring.go:286)
        raw = log_weighted_floor(
            cnt, size, (valid & cons.topo_known)[:, :, None] & has_key,
            N + 1)
        raw = jnp.where(ignored, 0.0, raw)

        # NormalizeScore (scoring.go:210-257): min/max over non-ignored filtered
        sel = scored
        big = jnp.float32(2**62)
        min_s = jnp.min(jnp.where(sel, raw, big), axis=1, keepdims=True)
        max_s = jnp.max(jnp.where(sel, raw, -big), axis=1, keepdims=True)
        max_s = jnp.maximum(max_s, 0.0)
        norm = jnp.where(max_s > 0,
                         _idiv(MAX_NODE_SCORE * (max_s + jnp.minimum(min_s, big)
                                                 - raw), jnp.maximum(max_s, 1.0)),
                         MAX_NODE_SCORE)
        out = jnp.where(ignored, 0.0, norm)
        # no soft constraints => every filtered node scores MaxNodeScore (the
        # reference's maxScore==0 branch)
        has_any = jnp.any(valid, axis=1, keepdims=True)
        out = jnp.where(has_any, out, MAX_NODE_SCORE)
        return jnp.where(feasible, out, 0.0)

    def dead():
        # no valid constraint on any pod: has_any is False on every row
        return jnp.where(feasible, jnp.float32(MAX_NODE_SCORE),
                         jnp.float32(0.0))

    # the profiler's op metadata names everything below (Perfetto, xprof)
    with jax.named_scope("spread_soft_score"):
        return _if_live(jnp.any(cons.valid), live, dead)


def spread_soft_skew(cluster, batch, match_ns=None) -> jnp.ndarray:
    """Diagnostics, not a score: for the batch's FIRST valid ScheduleAnyway
    constraint, the most less the least count of the pods its selector
    matches (its owner's namespace, terminating pods left out), over the
    pairs of its topology key that a valid node carries; i32, -1 where the
    batch has no such constraint.  ScheduleAnyway bounds no skew: this is
    what a cycle left behind.  One row's counts by pair id (P + N + L
    additions, no [., P] x [P, N] product), behind the set's gate.
    match_ns: the hoisted spread_match_ns ([Us, P])."""
    cons = batch.spread_soft
    C = cons.topo_key.shape[1]
    L = cluster.kv.shape[1]

    def live():
        row = jnp.argmax((cons.valid & cons.topo_known).reshape(-1))
        pre = (_pod_axis_match(cluster, cons.sel) if match_ns is None
               else match_ns)
        ns_ok = jnp.einsum("n,pn->p", batch.ns_hot[row // C],
                           cluster.pod_ns_hot,
                           preferred_element_type=jnp.float32) > 0.5
        m = (pre[jnp.asarray(cons.sel.index).reshape(-1)[row]] & ns_ok
             & cluster.pod_valid & ~cluster.pod_terminating)      # [P]
        key = cons.topo_key.reshape(-1)[row][None]
        cnt = pair_scatter(m[None, :], pod_topo_pairs(cluster, key), L)[0]
        node_pair = jnp.where(cluster.node_valid[None, :],
                              node_topo_pairs(cluster, key), -1)
        on = pair_scatter(jnp.ones_like(node_pair), node_pair, L)[0] > 0.5
        big = jnp.float32(2**31)
        skew = (jnp.max(jnp.where(on, cnt, -big))
                - jnp.min(jnp.where(on, cnt, big)))
        return jnp.where(jnp.any(on), skew, -1.0).astype(jnp.int32)

    return _if_live(jnp.any(cons.valid & cons.topo_known), live,
                    lambda: jnp.int32(-1))


# ---------------------------------------------------------------------------
# InterPodAffinity


def _pod_term_matches(cluster, terms, B: int, pre=None) -> jnp.ndarray:
    """Match pod-side affinity terms against existing pods -> [B, T, P]:
    selector x namespace x pod_valid.  pre: the terms' unique-selector
    match [U, P] (_pod_axis_pre), the assignment-independent part gang
    mode hoists out of its rounds."""
    if pre is None:
        pre = _pod_axis_match(cluster, terms.sel)
    T = terms.valid.shape[1]
    m = jnp.take(pre, terms.sel.index, axis=0).reshape(B, T, -1)
    ns_ok = jnp.einsum("btn,pn->btp", terms.ns_hot, cluster.pod_ns_hot,
                       preferred_element_type=jnp.float32) > 0.5
    return m & ns_ok & cluster.pod_valid[None, None, :]


def existing_terms_match(terms, batch) -> jnp.ndarray:
    """[Et, B] existing-pod term-selector x namespace x validity match
    against the batch — assignment-independent."""
    em = match_selectors(terms.sel, batch.kv_hot, batch.key_hot)
    ens = jnp.einsum("en,bn->eb", terms.ns_hot, batch.ns_hot,
                     preferred_element_type=jnp.float32) > 0.5
    return em & ens & terms.valid[:, None]


def _owner_pairs(cluster, terms):
    """(e_pair [E], owner_ok [E]) of existing pods' terms: the (key, value)
    pair each term's owner pins on its node, -1 for an invalid term or an
    owner that is gone or unplaced.  Gathers E rows, never the pod axis."""
    owner = jnp.clip(terms.pod_idx, 0, None)
    owner_node = jnp.take(cluster.pod_node, owner)
    owner_ok = jnp.take(cluster.pod_valid, owner) & (owner_node >= 0)
    owner_tp = jnp.take(cluster.topo_pair, jnp.clip(owner_node, 0, None),
                        axis=0)  # [E, TK]
    e_pair = jnp.take_along_axis(owner_tp, terms.topo_key[:, None],
                                 axis=1)[:, 0]
    return jnp.where(terms.valid & owner_ok, e_pair, -1), owner_ok


class InterpodPre(NamedTuple):
    """Assignment-independent matches for interpod_filter, precomputable
    once for gang mode's per-round re-evaluation."""
    m_ra: jnp.ndarray   # [Ur, P] unique-selector match (_pod_axis_pre)
    m_raa: jnp.ndarray  # [Ua, P]
    em: jnp.ndarray     # [Et, B]


def interpod_filter_pre(cluster, batch) -> InterpodPre:
    return InterpodPre(
        m_ra=_pod_axis_pre(cluster, batch.ra.sel, jnp.any(batch.ra.valid)),
        m_raa=_pod_axis_pre(cluster, batch.raa.sel,
                            jnp.any(batch.raa.valid)),
        em=existing_terms_match(cluster.filter_terms, batch))


def interpod_filter(cluster, batch,
                    pre: InterpodPre | None = None,
                    return_no_matches: bool = False,
                    active_keys=None):
    """InterPodAffinity filter.  Returns (ok, affinity_unresolvable) where
    affinity_unresolvable marks required-affinity failures
    (UnschedulableAndUnresolvable, reference: filtering.go:371-396).
    With return_no_matches, also returns the [B] bool marking pods whose
    required-affinity terms currently match nothing — i.e. the self-match
    bootstrap branch (filtering.go:356) is what admits them."""
    B = batch.req.shape[0]
    N = cluster.allocatable.shape[0]
    m_ra, m_raa, em = (None, None, None) if pre is None else pre
    if em is None:
        em = existing_terms_match(cluster.filter_terms, batch)  # [Et, B]

    # --- incoming required affinity (filtering.go:342 satisfyPodAffinity)
    ra = batch.ra
    Tr = ra.valid.shape[1]
    keys_r = jnp.where(ra.topo_known, ra.topo_key, -1).reshape(-1)

    def ra_live():
        m = _pod_term_matches(cluster, ra, B, pre=m_ra)  # [B, T, P]
        match_all = jnp.all(m | ~ra.valid[:, :, None], axis=1)  # [B, P]
        contrib = jnp.broadcast_to(match_all[:, None, :],
                                   m.shape).reshape(B * Tr, -1)
        cnt = _samepair_pods_to_nodes(cluster, contrib, keys_r,
                                      cluster.pod_node, cluster.pod_valid,
                                      active_keys=active_keys)
        # "matches anywhere" counts matching pods on key-carrying nodes over
        # VALID terms only (the reference's topologyToMatchedAffinityTerms
        # map has entries only for (term, key-bearing-node) pods).
        pod_tp = jnp.take(cluster.topo_pair,
                          jnp.clip(cluster.pod_node, 0, None),
                          axis=0)  # [P, TK]
        pod_keyed = (jnp.take(pod_tp.T, jnp.clip(keys_r, 0, None),
                              axis=0) >= 0) \
            & (keys_r >= 0)[:, None] \
            & (cluster.pod_node >= 0)[None, :] & cluster.pod_valid[None, :]
        # bool -> f32 cast, not where(mask, 1.0, 0.0): two Python-float
        # branches COMMIT to the default float dtype, so the count silently
        # becomes f64 wherever x64 is enabled (census/f64-promotion)
        tot = jnp.sum((pod_keyed & contrib
                       & ra.valid.reshape(-1)[:, None]).astype(jnp.float32),
                      axis=1)  # [B*Tr]
        return (cnt > 0.5).reshape(B, Tr, N), tot

    def ra_dead():
        # no valid term: term_ok is masked out of aff_ok on every row, and
        # tot counts over valid terms only
        return (jnp.zeros((B, Tr, N), bool),
                jnp.zeros((B * Tr,), jnp.float32))

    matched, tot = _if_live(jnp.any(ra.valid), ra_live, ra_dead)
    has_ra = jnp.any(ra.valid, axis=1)  # [B]
    node_pair = node_topo_pairs(cluster, ra.topo_key.reshape(-1))  # [B*T, N]
    node_has_key = (node_pair >= 0).reshape(B, Tr, N) & ra.topo_known[:, :, None]
    term_ok = node_has_key & matched
    aff_ok = jnp.all(term_ok | ~ra.valid[:, :, None], axis=1)
    # bootstrap: no matches anywhere + pod matches its own terms
    # (filtering.go:356-366); node must still carry every topology key.
    no_matches = jnp.sum(tot.reshape(B, Tr), axis=1) < 0.5
    self_all = jnp.all(ra.self_match | ~ra.valid, axis=1) & has_ra
    all_keys = jnp.all(node_has_key | ~ra.valid[:, :, None], axis=1)
    aff_ok = aff_ok | ((no_matches & self_all)[:, None] & all_keys)
    aff_ok = jnp.where(has_ra[:, None], aff_ok, True)

    # --- incoming required anti-affinity (filtering.go:329 satisfyPodAntiAffinity)
    raa = batch.raa
    Ta = raa.valid.shape[1]
    keys_a = jnp.where(raa.topo_known, raa.topo_key, -1).reshape(-1)

    def raa_live():
        ma = _pod_term_matches(cluster, raa, B,
                               pre=m_raa).reshape(B * Ta, -1)
        cnt_a = _samepair_pods_to_nodes(cluster, ma, keys_a,
                                        cluster.pod_node, cluster.pod_valid,
                                        active_keys=active_keys)
        return (cnt_a > 0.5).reshape(B, Ta, N)

    matched_a = _if_live(jnp.any(raa.valid), raa_live,
                         lambda: jnp.zeros((B, Ta, N), bool))
    np_a = node_topo_pairs(cluster, raa.topo_key.reshape(-1))
    has_key_a = (np_a >= 0).reshape(B, Ta, N) & raa.topo_known[:, :, None]
    anti_fail = jnp.any(has_key_a & matched_a & raa.valid[:, :, None], axis=1)

    # --- existing pods' required anti-affinity
    # (filtering.go:314 satisfyExistingPodsAntiAffinity): each term's owner
    # pins one (key, value) pair; a node fails iff it shares that pair and
    # the incoming pod matches the term — an [Et, B] x [Et, N] contraction
    ft = cluster.filter_terms
    e_pair, _ = _owner_pairs(cluster, ft)  # [Et]
    node_pairs_e = jnp.take(cluster.topo_pair.T, ft.topo_key, axis=0)  # [Et, N]
    sp_rows = (node_pairs_e == e_pair[:, None]) & (e_pair >= 0)[:, None]
    exist_fail = jnp.einsum("eb,en->bn", em.astype(jnp.bfloat16),
                            sp_rows.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) > 0.5

    ok = aff_ok & ~anti_fail & ~exist_fail
    if return_no_matches:
        return ok, ~aff_ok, no_matches
    return ok, ~aff_ok


class InterpodScorePre(NamedTuple):
    m_pref: jnp.ndarray  # [Up, P] unique-selector match
    em: jnp.ndarray      # [Es, B]


def interpod_score_pre(cluster, batch) -> InterpodScorePre:
    return InterpodScorePre(
        m_pref=_pod_axis_pre(cluster, batch.pref.sel,
                             jnp.any(batch.pref.valid)),
        em=existing_terms_match(cluster.score_terms, batch))


def interpod_score_raw(cluster, batch,
                       pre: InterpodScorePre | None = None,
                       active_keys=None):
    """The assignment-dependent RAW half of interpod_score -> (raw [B, N],
    any_counts [B, 1]).  Split out so the tiled mesh auction
    (parallel/shardmap.py build_bundle) can precompute it once per
    auction (under intra_batch_topology=False the pod axis is frozen, so
    raw is round-invariant) and recompute only the feasibility-dependent
    normalization per round."""
    B = batch.req.shape[0]
    N = cluster.allocatable.shape[0]
    m_pref, em_s = (None, None) if pre is None else pre
    if em_s is None:
        em_s = existing_terms_match(cluster.score_terms, batch)  # [Es, B]

    # incoming pod's preferred terms vs existing pods
    pt = batch.pref
    T = pt.valid.shape[1]

    def pref_live():
        m = _pod_term_matches(cluster, pt, B, pre=m_pref)  # [B, T, P]
        data = (_f(m) * pt.weight[:, :, None] * _f(pt.valid)[:, :, None])
        keys_p = jnp.where(pt.topo_known, pt.topo_key, -1).reshape(-1)
        raw1 = _samepair_pods_to_nodes(cluster, data.reshape(B * T, -1),
                                       keys_p, cluster.pod_node,
                                       cluster.pod_valid,
                                       active_keys=active_keys)
        return jnp.sum(raw1.reshape(B, T, N), axis=1)  # [B, N]

    raw1 = _if_live(jnp.any(pt.valid), pref_live,
                    lambda: jnp.zeros((B, N), jnp.float32))

    # existing pods' terms vs incoming pod: each term pins its owner-node's
    # (key, value) pair; nodes sharing it receive the term weight
    st = cluster.score_terms
    e_pair, owner_ok = _owner_pairs(cluster, st)
    em = _f(em_s & owner_ok[:, None]) * st.weight[:, None]  # [Es, B]
    node_pairs_e = jnp.take(cluster.topo_pair.T, st.topo_key, axis=0)  # [Es, N]
    sp_rows = (node_pairs_e == e_pair[:, None]) & (e_pair >= 0)[:, None]
    raw2 = jnp.einsum("eb,en->bn", em.astype(jnp.bfloat16),
                      sp_rows.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)

    raw = raw1 + raw2

    # NormalizeScore skips entirely when the topologyScore map is empty.
    # Every counted pair lives on at least its owner's node, so "map
    # empty" == "raw zero at every node".
    any_counts = jnp.any(raw != 0, axis=1, keepdims=True)
    return raw, any_counts


def interpod_score(cluster, batch, feasible,
                   pre: InterpodScorePre | None = None,
                   active_keys=None) -> jnp.ndarray:
    """InterPodAffinity scoring, already normalized (reference: scoring.go).

    Node-space formulation: the (topologyKey, value) -> weight map becomes
    per-node weighted same-pair sums — MXU matmuls with bf16-exact inputs
    (weights are ints |w| <= 100; accumulation is f32)."""
    raw, any_counts = interpod_score_raw(cluster, batch, pre=pre,
                                         active_keys=active_keys)
    # NormalizeScore (scoring.go:237-271): min/max start at 0
    big = jnp.float32(2**62)
    max_c = jnp.maximum(jnp.max(jnp.where(feasible, raw, -big), axis=1,
                                keepdims=True), 0.0)
    min_c = jnp.minimum(jnp.min(jnp.where(feasible, raw, big), axis=1,
                                keepdims=True), 0.0)
    diff = max_c - min_c
    norm = jnp.where(diff > 0,
                     _idiv(MAX_NODE_SCORE * (raw - min_c),
                           jnp.maximum(diff, 1.0)),
                     0.0)
    out = jnp.where(any_counts, norm, raw)
    return jnp.where(feasible, out, 0.0)


# ---------------------------------------------------------------------------
# resource scorers


def _safe_den(cap):
    """Division guard that preserves sub-unit capacities: the old
    maximum(cap, 1.0) clamp silently zeroed fractions for capacities under
    one unit (e.g. byte-scale memory in the reference's test tables, which
    land below 1 MiB after channel conversion).  Only true zero is
    redirected (the caller masks that case)."""
    return jnp.where(cap > 0, cap, 1.0)


def _alloc_req(cluster, batch):
    """(requested-with-pod, allocatable) for cpu/mem using NonZeroRequested
    (reference: noderesources/resource_allocation.go:108-117)."""
    req_cpu = cluster.nonzero_requested[None, :, 0] + batch.nonzero_req[:, 0][:, None]
    req_mem = cluster.nonzero_requested[None, :, 1] + batch.nonzero_req[:, 1][:, None]
    alloc_cpu = cluster.allocatable[None, :, CH_CPU]
    alloc_mem = cluster.allocatable[None, :, CH_MEM]
    return req_cpu, req_mem, alloc_cpu, alloc_mem


def balanced_formula(req_cpu, req_mem, alloc_cpu, alloc_mem) -> jnp.ndarray:
    """(1 - |cpuFraction - memFraction|) * MaxNodeScore — the formula shared
    by the batch kernel and the sequential scan
    (reference: noderesources/balanced_allocation.go:83-113)."""
    cpu_frac = jnp.where(alloc_cpu > 0, req_cpu / _safe_den(alloc_cpu), 1.0)
    mem_frac = jnp.where(alloc_mem > 0, req_mem / _safe_den(alloc_mem), 1.0)
    diff = jnp.abs(cpu_frac - mem_frac)
    # the reference truncates a float64 product (balanced_allocation.go:103);
    # two f32 divisions can land an ulp under the true value (e.g.
    # 74.999997 for a true 75), so compensate before the floor.  The
    # epsilon must stay at ulp scale (~7.6e-6 at score 75): anything
    # larger would round UP true products legitimately within epsilon
    # below an integer, diverging from the reference's floor
    score = jnp.floor((1.0 - diff) * MAX_NODE_SCORE + 1e-5)
    return jnp.where((cpu_frac >= 1.0) | (mem_frac >= 1.0), 0.0, score)


def least_formula(req, cap) -> jnp.ndarray:
    """(capacity - requested) * MaxNodeScore / capacity
    (reference: least_allocated.go:95-117)."""
    s = _idiv((cap - req) * MAX_NODE_SCORE, _safe_den(cap))
    return jnp.where((cap <= 0) | (req > cap), 0.0, s)


def most_formula(req, cap) -> jnp.ndarray:
    """requested * MaxNodeScore / capacity (reference: most_allocated.go:101)."""
    s = _idiv(req * MAX_NODE_SCORE, _safe_den(cap))
    return jnp.where((cap <= 0) | (req > cap), 0.0, s)


def balanced_allocation_score(cluster, batch) -> jnp.ndarray:
    return balanced_formula(*_alloc_req(cluster, batch))


def _weighted_resource_score(cluster, batch, per_resource, cpu_weight=1.0,
                             mem_weight=1.0) -> jnp.ndarray:
    req_cpu, req_mem, alloc_cpu, alloc_mem = _alloc_req(cluster, batch)
    s_cpu = per_resource(req_cpu, alloc_cpu)
    s_mem = per_resource(req_mem, alloc_mem)
    total = s_cpu * cpu_weight + s_mem * mem_weight
    return _idiv(total, cpu_weight + mem_weight)


def least_allocated_score(cluster, batch) -> jnp.ndarray:
    return _weighted_resource_score(cluster, batch, least_formula)


def most_allocated_score(cluster, batch) -> jnp.ndarray:
    return _weighted_resource_score(cluster, batch, most_formula)


# ---------------------------------------------------------------------------
# remaining scorers


def node_affinity_score(cluster, batch) -> jnp.ndarray:
    """Sum of matched preferred node-affinity term weights (raw; normalized
    by default_normalize) (reference: nodeaffinity/node_affinity.go:65-103)."""
    B = batch.req.shape[0]
    Tp = batch.pna_valid.shape[1]
    m = match_selectors(batch.pna_sel, cluster.kv, cluster.keymask, cluster.num)
    m = m.reshape(B, Tp, -1)
    w = batch.pna_weight * _f(batch.pna_valid)
    return jnp.einsum("bt,btn->bn", w, _f(m), preferred_element_type=jnp.float32)


def taint_toleration_score(cluster, batch) -> jnp.ndarray:
    """Count of untolerated PreferNoSchedule taints (raw; reverse-normalized)
    (reference: tainttoleration/taint_toleration.go:123-141)."""
    untol_prefer = _f(~batch.tolerated) * _f(cluster.taint_is_prefer)[None, :]
    return jnp.einsum("bt,nt->bn", untol_prefer, _f(cluster.taints),
                      preferred_element_type=jnp.float32)


_MB = 1024.0 * 1024.0
IMAGE_MIN_THRESHOLD = 23.0 * _MB       # reference: image_locality.go:44
IMAGE_MAX_CONTAINER_THRESHOLD = 1000.0 * _MB


def image_locality_score(cluster, batch) -> jnp.ndarray:
    """Scaled sum of present image sizes (reference: image_locality.go:82-110)."""
    scaled = _f(cluster.images) * jnp.floor(cluster.image_size
                                            * cluster.image_spread)[None, :]
    s = jnp.einsum("bi,ni->bn", batch.images_hot, scaled,
                   preferred_element_type=jnp.float32)
    max_thr = IMAGE_MAX_CONTAINER_THRESHOLD * jnp.maximum(batch.n_containers, 1.0)
    s = jnp.clip(s, IMAGE_MIN_THRESHOLD, max_thr[:, None])
    return _idiv(MAX_NODE_SCORE * (s - IMAGE_MIN_THRESHOLD),
                 max_thr[:, None] - IMAGE_MIN_THRESHOLD)


def prefer_avoid_pods_score(cluster, batch) -> jnp.ndarray:
    """MaxNodeScore unless the node's preferAvoidPods annotation names the
    pod's RC/RS controller (reference: node_prefer_avoid_pods.go:46-81)."""
    hit = jnp.take(cluster.avoid_hot.T, jnp.clip(batch.avoid_id, 0, None), axis=0)
    avoided = hit & (batch.avoid_id >= 0)[:, None]
    return jnp.where(avoided, 0.0, MAX_NODE_SCORE)


def _default_spread_live(batch) -> jnp.ndarray:
    """Device bool: some pod of the batch can score a nonzero
    DefaultPodTopologySpread count — it has a controller selector (a nil
    selector matches nothing, selectors.py sel_valid) and does not skip
    the plugin for explicit constraints of its own."""
    sel = batch.spread_selector
    return jnp.any(jnp.take(sel.sel_valid, sel.index) & ~batch.spread_skip)


def _default_spread_match_ns(cluster, batch, pre=None) -> jnp.ndarray:
    """[B, P] DefaultPodTopologySpread selector x namespace match —
    assignment-independent.  pre: the controller selectors' unique match
    [U, P] (default_spread_match_ns)."""
    if pre is None:
        pre = _pod_axis_match(cluster, batch.spread_selector)
    m = jnp.take(pre, batch.spread_selector.index, axis=0)
    ns_ok = jnp.einsum("bn,pn->bp", batch.ns_hot, cluster.pod_ns_hot,
                       preferred_element_type=jnp.float32) > 0.5
    return m & ns_ok


def default_spread_match_ns(cluster, batch) -> jnp.ndarray:
    """The hoisted pre of the controller selectors ([U, P],
    _pod_axis_pre)."""
    return _pod_axis_pre(cluster, batch.spread_selector,
                         _default_spread_live(batch))


def default_spread_score(cluster, batch, match_ns=None) -> jnp.ndarray:
    """DefaultPodTopologySpread raw score: count of same-namespace,
    non-terminating pods on the node matched by the combined controller
    selector (reference: default_pod_topology_spread.go:74-97, 200-215)."""
    B = batch.spread_skip.shape[0]
    N = cluster.allocatable.shape[0]

    def live():
        m_ns = _default_spread_match_ns(cluster, batch, pre=match_ns)
        countable = cluster.pod_valid & ~cluster.pod_terminating
        counts = per_node_counts(m_ns & countable[None, :],
                                 cluster.pod_node, N)
        return jnp.where(batch.spread_skip[:, None], 0.0, counts)

    return _if_live(_default_spread_live(batch), live,
                    lambda: jnp.zeros((B, N), jnp.float32))


ZONE_WEIGHTING = 2.0 / 3.0  # reference: default_pod_topology_spread.go:44


def default_spread_normalize(cluster, batch, raw, feasible) -> jnp.ndarray:
    """Zone-aware normalization (reference: default_pod_topology_spread.go:104-166).

    Zone aggregation rides cluster.zone_hot [N, Z] with Z = the zone-vocab
    bucket (typically 8-16), so both the per-zone sum and the
    gather-back-to-nodes are tiny [., Z] matmuls.  The earlier formulation
    used an [N, N] zone one-hot: at 8k nodes its HIGHEST-precision
    [B, N] x [N, N] contraction plus an [B, N] gather was ~800 ms/round —
    the single largest op in the gang auction."""
    big = jnp.float32(2**62)
    raw_f = jnp.where(feasible, raw, 0.0)
    max_node = jnp.max(jnp.where(feasible, raw, -big), axis=1, keepdims=True)
    max_node = jnp.maximum(max_node, 0.0)

    zh = cluster.zone_hot  # [N, Z]; zero rows for zoneless/invalid nodes
    has_zone = jnp.any(zh > 0, axis=1)  # [N]
    counts_by_zone = jnp.einsum("bn,nz->bz", raw_f, zh,
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)  # [B, Z]
    have_zones = jnp.any(feasible & has_zone[None, :], axis=1, keepdims=True)
    max_zone = jnp.maximum(jnp.max(counts_by_zone, axis=1, keepdims=True), 0.0)

    f_score = jnp.where(max_node > 0,
                        MAX_NODE_SCORE * (max_node - raw) / jnp.maximum(max_node, 1.0),  # kubelint: ignore[numeric/score-div] reference computes fScore in float64 (default_pod_topology_spread.go:126); floor lands after the zone combine
                        MAX_NODE_SCORE)
    # one nonzero term per output (one-hot) => exact regardless of precision
    node_zone_count = jnp.einsum("bz,nz->bn", counts_by_zone, zh,
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
    zone_score = jnp.where(max_zone > 0,
                           MAX_NODE_SCORE * (max_zone - node_zone_count)  # kubelint: ignore[numeric/score-div] reference computes zoneScore in float64 (default_pod_topology_spread.go:142); floor lands after the combine
                           / jnp.maximum(max_zone, 1.0),
                           MAX_NODE_SCORE)
    with_zone = (f_score * (1.0 - ZONE_WEIGHTING)) + ZONE_WEIGHTING * zone_score
    out = jnp.where(have_zones & has_zone[None, :], with_zone, f_score)
    out = jnp.floor(out)
    out = jnp.where(batch.spread_skip[:, None], 0.0, out)
    return jnp.where(feasible, out, 0.0)


# ---------------------------------------------------------------------------
# normalization helpers


def default_normalize(raw, feasible, reverse: bool) -> jnp.ndarray:
    """reference: plugins/helper/normalize_score.go:26 (DefaultNormalizeScore)."""
    big = jnp.float32(2**62)
    max_c = jnp.maximum(jnp.max(jnp.where(feasible, raw, -big), axis=1,
                                keepdims=True), 0.0)
    scaled = _idiv(MAX_NODE_SCORE * raw, jnp.maximum(max_c, 1.0))
    if reverse:
        scaled = MAX_NODE_SCORE - scaled
    zero_case = MAX_NODE_SCORE if reverse else 0.0
    out = jnp.where(max_c > 0, scaled, zero_case)
    return jnp.where(feasible, out, 0.0)


# ---------------------------------------------------------------------------
# configurable scorers (plugin-args driven)


def _itrunc(a, b):
    """Go int64 division truncates toward ZERO (not floor); b > 0."""
    q = _idiv(jnp.abs(a), b)
    return jnp.where(a < 0, -q, q)


def broken_linear(p, shape):
    """Piecewise-linear shape function with Go integer-division semantics
    (reference: noderesources/requested_to_capacity_ratio.go:158
    buildBrokenLinearFunction).  shape: static tuple of (utilization, score).
    Decreasing segments produce negative deltas, so the division must
    truncate toward zero like Go's, not floor."""
    out = jnp.full_like(p, float(shape[-1][1]))  # kubelint: ignore[host-sync/cast] trace-time constant: shape is the static plugin-args tuple
    for i in range(len(shape) - 1, -1, -1):
        u_i, s_i = float(shape[i][0]), float(shape[i][1])  # kubelint: ignore[host-sync/cast] trace-time constant: shape is the static plugin-args tuple
        if i == 0:
            seg = jnp.full_like(p, s_i)
        else:
            u_p, s_p = float(shape[i - 1][0]), float(shape[i - 1][1])  # kubelint: ignore[host-sync/cast] trace-time constant: shape is the static plugin-args tuple
            seg = s_p + _itrunc((s_i - s_p) * (p - u_p), u_i - u_p)
        out = jnp.where(p <= u_i, seg, out)
    return out


def rtcr_combine(parts, shape):
    """Weighted RequestedToCapacityRatio combine shared by the batch kernel
    and the sequential scan (reference: requested_to_capacity_ratio.go:
    124-147).  parts: iterable of (req, cap, weight) arrays; zero/exceeded
    capacity falls back to rawScoringFunction(maxUtilization); the final
    divide is math.Round (half away from zero) in exact integer form."""
    total = None
    weight_sum = None
    for req, cap, weight in parts:
        # _safe_den, not maximum(cap, 1): sub-unit capacities (byte-scale
        # memory after MiB conversion) must still divide by their true
        # value — the cap<=0 case is redirected to the fallback below
        util = 100.0 - _idiv((cap - req) * 100.0, _safe_den(cap))
        s = broken_linear(util, shape)
        s = jnp.where((cap <= 0) | (req > cap),
                      broken_linear(jnp.full_like(util, 100.0), shape), s)
        contrib = jnp.where(s > 0, s * weight, 0.0)
        w = jnp.where(s > 0, float(weight), 0.0)  # kubelint: ignore[host-sync/cast] trace-time constant: weight comes from the static resources tuple
        total = contrib if total is None else total + contrib
        weight_sum = w if weight_sum is None else weight_sum + w
    return jnp.where(weight_sum > 0,
                     _idiv(2.0 * total + weight_sum,
                           jnp.maximum(2.0 * weight_sum, 1.0)),
                     0.0)


def requested_to_capacity_ratio_score(cluster, batch, shape, resources) -> jnp.ndarray:
    """RequestedToCapacityRatio (reference: requested_to_capacity_ratio.go:
    124-147).  shape: ((utilization, score)...); resources: ((kind, ch,
    weight)...) with kind 0=cpu (NonZero), 1=memory (NonZero), 2=scalar
    channel ch.  Scores use math.Round (half away from zero)."""
    req_cpu, req_mem, alloc_cpu, alloc_mem = _alloc_req(cluster, batch)
    parts = []
    for kind, ch, weight in resources:
        if kind == 0:
            req, cap = req_cpu, alloc_cpu
        elif kind == 1:
            req, cap = req_mem, alloc_mem
        elif ch < 0:
            # resource name unknown to the cluster: capacity 0 everywhere
            # (Go falls to rawScoringFunction(maxUtilization))
            req = jnp.zeros_like(req_cpu)
            cap = jnp.zeros_like(alloc_cpu)
        else:
            cap = cluster.allocatable[None, :, ch]
            req = cluster.requested[None, :, ch] + batch.req[:, ch][:, None]
        parts.append((req, cap, weight))
    return rtcr_combine(parts, shape)


def resource_limits_score(cluster, batch) -> jnp.ndarray:
    """NodeResourceLimits: 1 if the node satisfies the pod's cpu or memory
    *limit* (reference: noderesources/resource_limits.go:104-123,155)."""
    lim_cpu = batch.limits[:, None, CH_CPU]
    lim_mem = batch.limits[:, None, CH_MEM]
    alloc_cpu = cluster.allocatable[None, :, CH_CPU]
    alloc_mem = cluster.allocatable[None, :, CH_MEM]
    cpu_ok = (lim_cpu > 0) & (alloc_cpu > 0) & (lim_cpu <= alloc_cpu)
    mem_ok = (lim_mem > 0) & (alloc_mem > 0) & (lim_mem <= alloc_mem)
    return jnp.where(cpu_ok | mem_ok, 1.0, 0.0)


def node_label_filter(cluster, batch, present_ids, absent_ids) -> jnp.ndarray:
    """NodeLabel filter: all configured present labels present, absent ones
    absent (reference: nodelabel/node_label.go:48-68).  ids are key-vocab
    ids; -1 means the label exists nowhere in the cluster."""
    B = batch.req.shape[0]
    N = cluster.keymask.shape[0]
    ok = jnp.ones((N,), bool)
    for kid in present_ids:
        ok = ok & (cluster.keymask[:, kid] if kid >= 0
                   else jnp.zeros((N,), bool))
    for kid in absent_ids:
        ok = ok & (~cluster.keymask[:, kid] if kid >= 0
                   else jnp.ones((N,), bool))
    return jnp.broadcast_to(ok[None, :], (B, N))


def node_label_score(cluster, batch, prefs) -> jnp.ndarray:
    """NodeLabel score: average of MaxNodeScore per satisfied preference
    (reference: nodelabel/node_label.go:70-93).  prefs: ((key_id,
    want_present)...)."""
    B = batch.req.shape[0]
    N = cluster.keymask.shape[0]
    if not prefs:
        return jnp.zeros((B, N), jnp.float32)
    score = jnp.zeros((N,), jnp.float32)
    for kid, want_present in prefs:
        has = (cluster.keymask[:, kid] if kid >= 0
               else jnp.zeros((N,), bool))
        hit = has if want_present else ~has
        score = score + jnp.where(hit, MAX_NODE_SCORE, 0.0)
    score = _idiv(score, float(len(prefs)))
    return jnp.broadcast_to(score[None, :], (B, N))
