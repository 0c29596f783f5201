"""Conflict-free batched (gang) assignment: propose-and-admit auction.

The reference schedules one pod per cycle, so intra-batch capacity conflicts
cannot happen (reference: pkg/scheduler/scheduler.go:509 scheduleOne).  The
naive batched program (programs.schedule_batch) scores every pod against the
same snapshot, so two pods can both claim the last slot of a node.  The
sequential scan (models/sequential.py) is exact but pays O(B) serial steps.

This module is the third mode: a parallel auction in the family of Bertsekas'
assignment auctions, specialised to the scheduler's one-sided capacity
constraints.  Each round, entirely on device:

1. every unassigned pod *proposes* to its argmax feasible node, using the
   same per-pod tie-break RNG as the sequential replay
   (jax.random.fold_in(rng, pod_index) — selectHost semantics,
   generic_scheduler.go:217);
2. pods proposing the same node are *admitted* in pod order (the batch is
   popped from the queue in priority order, so pod index = the reference's
   serial order) up to the node's remaining multi-resource capacity and
   hostPort set.  Admission is a sort by proposed node + a segmented
   prefix-sum over request channels — no [B, N, R] intermediate, so it
   scales to 100k x 10k;
3. admitted placements commit: node requested/ports update, and the next
   round recomputes feasibility *and scores* against the updated usage
   (pods placed in later rounds see earlier rounds' placements, the batched
   analog of the serial loop's assume; capacity semantics exactly match
   noderesources/fit.go:194-267 + NodePorts).

Invariants:
- zero capacity violations: an admitted pod's request fits within
  free-capacity-minus-earlier-proposers (a superset of earlier admitted),
  and a pod whose probed hostPorts collide with any earlier proposer's
  registered ports is deferred to the next round;
- progress: the first proposer of every proposed-to node always fits (the
  node was feasible for it this round), so each round either admits >=1 pod
  or proves the remaining pods unschedulable — the loop terminates (a
  round that proposed past the strict spread verdict is followed by a
  strict one before it proves anything: see below);
- uncontended agreement: when every pod's argmax is distinct and capacity
  suffices, round 1 admits every pod at exactly the node the sequential
  replay picks under the same rng.

Topology correctness (intra-batch): the batch's pods are appended to the
snapshot's existing-pod axis once, and each round updates their
pod_node/pod_valid from the carry, so PodTopologySpread and InterPodAffinity
filters (and the topology scores) are re-evaluated against committed
placements exactly — a pod admitted in round r sees every pod admitted in
rounds < r the way the reference's serial loop sees previously bound pods
(interpodaffinity/filtering.go:314, podtopologyspread/filtering.go:200).
Admitted pods' own required anti-affinity terms are spliced into
filter_terms so they repel later-round pods (the existing-pods direction),
and their preferred and required-affinity terms into score_terms, so they
score later-round pods the way bound pods' terms do (_extend_cluster).
Within a round, a conservative same-topology-pair deferral keeps admission
order safe for InterPodAffinity.  A pod with a required anti-affinity term
is deferred to the next round if any earlier-index pod matching that term
was admitted this round into the topo pair the term's key maps its proposal
to (and any pod is deferred from a pair an earlier-admitted pod whose
anti-affinity term selects it landed in); a pod admitted only by the
self-match bootstrap defers behind any earlier admission.  The earlier pods
these rules count are the capacity-admitted ones: a superset of the
admitted, so they err high.  They never block the first admitted pod.

A hard (DoNotSchedule) spread constraint is held IN POD ORDER inside the
round, at the pod's turn and not at the round's start (spread_turns).  The
filter of the round (K.spread_filter, return_slack) hands back, from the
intermediates of its verdict, slack = maxSkew - (matchNum + selfMatch -
minMatch) on every node and floor, the matching pods of every pair
REGISTERED for the constraint (the pairs its minimum runs over).  One serial
pass over the window's rows, which are ascending batch indices, so window
order is the full round's order, then admits pod j iff for each of its
valid constraints c, with p its proposal's pair under c's key,

    matchNum0[p] + e + selfMatch - (minMatch0 + rise) <= maxSkew,
    i.e.  e - rise <= slack at the proposal,

e the pods admitted before j THIS round that c's selector matches and that
landed in p, rise = min over c's registered pairs of (floor + this round's
matching admits there) - minMatch0 >= 0.  That is PodTopologySpread's
filter as the serial loop evaluates it at j's turn, exactly, because:
- the pass runs LAST, on the pods capacity, rules A / B and the bootstrap
  rule admit, and counts a pod only once this rule itself has admitted it:
  a pod held back lifts nobody's minimum and uses up nobody's room;
- the filter here counts EVERY node's pods into a registered pair
  (spread_filter: cnt runs over all nodes, registered over the pod's
  eligible ones), so an admitted pod counts for j wherever in the pair it
  landed: no eligibility test on the landing node is needed, none is made;
- the minimum runs over j's registered pairs only: an unregistered pair
  carries _UNREGISTERED in floor, counts 0 pods at the proposal (the
  reference's nil tpCount) and is in no minimum;
- slack is +inf where the filter tolerates the pod (no valid constraint,
  the empty preFilterState): such a pod is never held back;
- nothing leaves inside a round and the registered pairs do not depend on
  the carry, so the counts only rise.
The round's admits are counted by UNIQUE selector, which does not read the
namespace: a selector whose community (the pods it matches, the pods whose
constraints use it) spans two namespaces would be over-counted, so for such
a one (sph_mixed, read from the batch) e keeps the over-count and rise is 0:
the round-start minimum, PR 34's rule, which errs to the safe side on both.
No cell and no upstream row has such a selector.

Proposals run over every pair that can OPEN inside the round: the verdict
that masks the proposals loosens the skew test alone to skew <= maxSkew +
r, r = (the window's unassigned pods) // (c's registered pairs), how far a
round whose admits fall level over the pairs lifts the minimum (three zones
and 512 pods: 170, wide open; a hostname constraint over 5,000 nodes: 0,
the strict verdict to the letter; 0 too for an sph_mixed selector).  Without
it a zone that stands at minimum + maxSkew as the round starts is proposed
by nobody, the others rise 2 x maxSkew above it and stop, and the roles
swap every round.  Correctness never rests on r: only the pass admits; r
decides how many proposals a round wastes.  A pod the pass held back draws
its tie-break from the next stream of its key (fold_in by the times it was
held): its own stream is fixed for the cycle, so it would draw the same
node while that stays in its tie set, and the pods a widened round leaves
over are exactly those whose favourite lies in the full pair (read on the
CPU at the spread cell's size: 8-10 rounds a cycle without, 4 with).  A pod
never held back, any pod of a batch without a hard constraint, draws as the
sequential replay does.  Round 0's capture (feasible0,
n_feasible, the preemption gate) keeps the STRICT verdict.  PROGRESS is kept
by construction: a round that proposed past the strict verdict and admitted
nobody proves nothing, so the next one proposes on the strict verdict,
today's round, whose first proposer always fits (e = 0, rise = 0, slack >=
0); the loop ends, and the windowed loop retires a pod, only on a strict
round that admits nothing.  Both halves sit behind the filter's own runtime
gate (any valid hard constraint in the batch): a batch without one runs no
pass, its widened verdict is its strict one, and its placements are the
parent's bit for bit.

Score staleness within a single round (not across rounds) is the remaining
gap vs the sequential replay mode; since the proposals run over the widened
verdict it covers the spread verdict too: NormalizeScore runs over the
widened feasible set, and a pod may hold a node whose pair was full as its
round started.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..ops import kernels as K
from ..ops.selectors import concat_selector_sets, match_selectors_unique
from ..state.tensors import ExistingTerms
from .programs import ProgramConfig, run_filters, run_scores

_f = K._f
_NEG = jnp.float32(-2**62)


class GangResult(NamedTuple):
    chosen: jnp.ndarray     # [B] i32 node row, -1 unschedulable this pass
    score: jnp.ndarray      # [B] f32 score of the winning node at admission
    rounds: jnp.ndarray     # i32 number of propose/admit rounds executed
    requested: jnp.ndarray  # [N, R] final requested incl. batch placements
    nz: jnp.ndarray         # [N, 2] final non-zero requested
    ports_used: jnp.ndarray  # [N, P] f32 ports registered by batch placements
    feasible0: jnp.ndarray  # [B, N] bool first-round feasibility (diagnostics)
    unresolvable: jnp.ndarray  # [B, N] bool — static filters plus the
                            # InterPodAffinity required-affinity bits
                            # re-captured at round 0 when intra-batch
                            # topology moves that filter into the loop
    n_feasible: jnp.ndarray    # [B] i32 first-round feasible-node count
    all_unresolvable: jnp.ndarray  # [B] bool — every failed node failed
                            # UnschedulableAndUnresolvable (preemption gate,
                            # scheduler.go:391; matches SeqResult's field)
    packed: jnp.ndarray     # [3*B + 1] i32 = concat(chosen, n_feasible,
                            # all_unresolvable, [rounds]) — the host's
                            # per-cycle view in ONE device->host readback
                            # (each readback is a host sync; the serving
                            # loop makes exactly one per cycle)
    capacity_deferred: jnp.ndarray  # i32 proposals, summed over the rounds,
                            # that found their node full at their turn
                            # (admission_mask refused them; topology
                            # deferrals are not counted).  Diagnostics: not
                            # in packed, read back only by an armed flight
                            # recorder, after packed
    soft_spread_skew: jnp.ndarray  # i32 K.spread_soft_skew after the last
                            # round's admits; -1 for a batch without a
                            # ScheduleAnyway constraint.  Diagnostics,
                            # read back as capacity_deferred is
    spread_late_admits: Optional[jnp.ndarray] = None  # i32 pods admitted,
                            # summed over the rounds, that the round-start
                            # rule would have held back: their node failed
                            # the round-start skew test, or their pair's
                            # round-start room was used up by their turn
                            # (spread_turns).  None (no output at all) for
                            # a program that does not re-evaluate
                            # PodTopologySpread in its rounds.
                            # Diagnostics, read back as capacity_deferred is
    affinity_bootstrap_admits: Optional[jnp.ndarray] = None  # i32 pods
                            # admitted, summed over the rounds, through
                            # the self-match bootstrap alone
                            # (filtering.go:356: their required-affinity
                            # terms matched no pod anywhere as their round
                            # started).  None for a program that does not
                            # re-evaluate InterPodAffinity in its rounds.
                            # Diagnostics, read back as capacity_deferred is


def _segment_base(values: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """For row-sorted segments: propagate each segment-start row's value
    forward.  values must be non-decreasing along axis 0 (cumsum outputs),
    so a cummax over (start ? value : -1) yields, at every row, the value at
    its segment's first row."""
    marked = jnp.where(is_start[:, None] if values.ndim == 2 else is_start,
                       values, -1.0)
    return jax.lax.cummax(marked, axis=0)


def _term_rows(terms, weight, owner0: int, n_topo_keys: int):
    """A batch term set ([B, T] companions over a flat [B*T] selector set)
    as ExistingTerms rows owned by pod-axis rows owner0 + j.  A term whose
    topology key exists nowhere in the cluster can never produce a pair, so
    it never counts for or against anything — its row is invalid."""
    B, T = terms.valid.shape
    return ExistingTerms(
        sel=terms.sel,
        ns_hot=terms.ns_hot.reshape(B * T, -1),
        topo_key=terms.topo_key.reshape(-1),
        pod_idx=owner0 + jnp.repeat(jnp.arange(B, dtype=jnp.int32), T),
        weight=weight.reshape(-1),
        valid=(terms.valid & terms.topo_known
               & (terms.topo_key < n_topo_keys)).reshape(-1))


def _append_terms(table: ExistingTerms, *more: ExistingTerms) -> ExistingTerms:
    """table's rows followed by each of more's, in order."""
    sel = table.sel
    for m in more:
        sel = concat_selector_sets(sel, m.sel)
    return ExistingTerms(
        sel=sel,
        **{f: jnp.concatenate([getattr(table, f)]
                              + [getattr(m, f) for m in more])
           for f in ExistingTerms._fields if f != "sel"})


def _splice_score_terms(cluster, batch, owner0: int,
                        sets=("pref", "ra"), hard_pod_affinity_weight=1.0):
    """score_terms with the batch pods' own score-side terms appended,
    owner rows owner0 + j: what a FRESH build puts there for a bound pod
    (state/tensors.py SnapshotBuilder: preferred affinity and
    anti-affinity at their signed weights, required affinity at
    hardPodAffinityWeight; scoring.go processExistingPod), in the order of
    `sets`.  A row counts once its owner is valid on the pod axis at a
    node (ops/kernels.py _owner_pairs)."""
    TK = cluster.topo_pair.shape[1]
    rows = []
    for name in sets:
        t = getattr(batch, name)
        w = (t.weight if name == "pref"
             else jnp.full_like(t.weight, hard_pod_affinity_weight))
        rows.append(_term_rows(t, w * _f(t.valid), owner0, TK))
    return _append_terms(cluster.score_terms, *rows)


def _extend_cluster(cluster, batch, score_sets=(),
                    hard_pod_affinity_weight=1.0):
    """Append the batch's pods to the existing-pod axis (pod_node/pod_valid
    are patched per round from the carry) and splice their terms into the
    existing pods' term tables with owner rows P+j, so a batch pod admitted
    in round r acts on the pods of later rounds exactly like a bound
    existing pod:

    - filter_terms gets every pod's required anti-affinity terms, so
      admitted batch pods repel (interpodaffinity/filtering.go:166
      getExistingAntiAffinityCounts);
    - score_terms gets the term sets named in score_sets
      (_splice_score_terms: "pref", "ra"), so admitted batch pods' own
      preferred and required-affinity terms score the nodes they landed
      on for the pods they select (scoring.go processExistingPod).
      score_sets is STATIC (ProgramConfig.batch_score_sets, which the
      scheduler reads off the batch): a batch without a score-side term
      names none and appends no row."""
    B = batch.req.shape[0]
    P = cluster.pod_valid.shape[0]
    raa = batch.raa
    Ta = raa.valid.shape[1]
    TK = cluster.topo_pair.shape[1]
    ft = cluster.filter_terms
    topo_key = raa.topo_key.reshape(-1)
    # a term whose topology key exists nowhere in the cluster can never
    # produce a pair, so it never fails anything — drop it
    valid = (raa.valid & raa.topo_known
             & (raa.topo_key < TK)).reshape(-1)
    ext_terms = ExistingTerms(
        sel=concat_selector_sets(ft.sel, raa.sel),
        ns_hot=jnp.concatenate([ft.ns_hot, raa.ns_hot.reshape(B * Ta, -1)]),
        topo_key=jnp.concatenate([ft.topo_key, topo_key]),
        pod_idx=jnp.concatenate(
            [ft.pod_idx, P + jnp.repeat(jnp.arange(B, dtype=jnp.int32), Ta)]),
        weight=jnp.concatenate([ft.weight, jnp.ones((B * Ta,), jnp.float32)]),
        valid=jnp.concatenate([ft.valid, valid]),
    )
    ext = cluster._replace(
        pod_kv=jnp.concatenate([cluster.pod_kv, batch.kv_hot]),
        pod_key=jnp.concatenate([cluster.pod_key, batch.key_hot]),
        pod_ns_hot=jnp.concatenate([cluster.pod_ns_hot, batch.ns_hot]),
        pod_node=jnp.concatenate(
            [cluster.pod_node, jnp.full((B,), -1, jnp.int32)]),
        pod_valid=jnp.concatenate(
            [cluster.pod_valid, jnp.zeros((B,), bool)]),
        pod_terminating=jnp.concatenate(
            [cluster.pod_terminating, jnp.zeros((B,), bool)]),
        filter_terms=ext_terms,
    )
    if score_sets:
        ext = ext._replace(score_terms=_splice_score_terms(
            cluster, batch, P, score_sets, hard_pod_affinity_weight))
    return ext


def _seg_prefix(e_sorted: jnp.ndarray, is_start: jnp.ndarray) -> jnp.ndarray:
    """Exclusive per-segment prefix sums of [B, U] rows sorted by segment."""
    cs = jnp.cumsum(e_sorted, axis=0)
    excl = cs - e_sorted
    return excl - _segment_base(excl, is_start)


def admission_mask(prop, active, req_b, ports_hot_b, ports_asnode_b,
                   allocatable, req_carry, use_ports: bool,
                   n_nodes: int) -> jnp.ndarray:
    """The segmented-reduce admission verdict over one round's proposals:
    sort by proposed node (stable keeps pod order — the batch is popped in
    priority order, so row index IS the reference's serial order), then
    admit each proposer iff its request fits the node's free capacity
    minus EARLIER proposers' requests (a superset of earlier admitted)
    and its probed hostPorts miss every earlier proposer's registered
    set.  Shared verbatim by the lax round (_round_tail) and the
    shard_map tiled round (parallel/shardmap.py) — ONE source of truth
    keeps the two paths bit-identical by construction.  prop must use
    n_nodes as the no-op segment for inactive pods."""
    order = jnp.argsort(prop, stable=True)
    snode = prop[order]
    sactive = active[order]
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), snode[1:] != snode[:-1]])
    sreq = req_b[order] * _f(sactive)[:, None]
    prefix_excl = _seg_prefix(sreq, is_start)
    node_safe = jnp.clip(snode, 0, n_nodes - 1)
    free = allocatable[node_safe] - req_carry[node_safe]
    cap_ok = K.fit_rows(req_b[order], free - prefix_excl)
    if use_ports:
        sreg = ports_asnode_b[order] * _f(sactive)[:, None]
        earlier_ports = _seg_prefix(sreg, is_start)
        conflict = jnp.sum(ports_hot_b[order] * earlier_ports,
                           axis=1) > 0.5
        cap_ok = cap_ok & ~conflict
    admit_sorted = cap_ok & sactive & (snode < n_nodes)
    return jnp.zeros(prop.shape, bool).at[order].set(admit_sorted)


def admission_sums(admit, prop, req_b, nonzero_b, ports_asnode_b,
                   use_ports: bool, n_nodes: int):
    """Commit-side segment sums of one round's admitted placements:
    (add_req [N, R], add_nz [N, 2], add_ports [N, P] | None).  Shared by
    _round_tail and the shard_map tiled round."""
    seg = jnp.where(admit, prop, n_nodes)
    add_req = jax.ops.segment_sum(
        req_b * _f(admit)[:, None], seg, num_segments=n_nodes + 1)[:n_nodes]
    add_nz = jax.ops.segment_sum(
        nonzero_b * _f(admit)[:, None], seg,
        num_segments=n_nodes + 1)[:n_nodes]
    add_ports = None
    if use_ports:
        add_ports = jax.ops.segment_max(
            ports_asnode_b * _f(admit)[:, None], seg,
            num_segments=n_nodes + 1)[:n_nodes]
    return add_req, add_nz, add_ports


def _key_terms_mask(terms, k: int) -> jnp.ndarray:
    """[B, T] bool — valid required terms on topology key k."""
    return (terms.topo_key == k) & terms.valid & terms.topo_known


def materialize_assigned(cluster, batch, chosen, requested, nz, ports_used,
                         pad_pods_to: int = 0, pad_terms_to: int = 0,
                         extend_score_terms: bool = False,
                         hard_pod_affinity_weight: float = 1.0):
    """Python entry for the jitted materialize — AOT seam (utils/aot.py):
    armed, a signature hit runs the deserialized build-time executable;
    disarmed this is the plain jit call.  See _materialize_assigned."""
    from ..utils import aot
    return aot.dispatch(
        "_materialize_assigned", _materialize_assigned,
        (cluster, batch, chosen, requested, nz, ports_used),
        dict(pad_pods_to=pad_pods_to, pad_terms_to=pad_terms_to,
             extend_score_terms=extend_score_terms,
             hard_pod_affinity_weight=hard_pod_affinity_weight),
        static_argnames=("pad_pods_to", "pad_terms_to",
                         "extend_score_terms"))


@functools.partial(jax.jit, static_argnames=("pad_pods_to", "pad_terms_to",
                                             "extend_score_terms"))
def _materialize_assigned(cluster, batch, chosen, requested, nz, ports_used,
                          pad_pods_to: int = 0, pad_terms_to: int = 0,
                          extend_score_terms: bool = False,
                          hard_pod_affinity_weight: float = 1.0):
    """Fold a (partial) auction's placements into the cluster: assigned
    batch pods join the existing-pod axis at their nodes, their committed
    usage replaces requested/nonzero, and their registered hostPorts join
    cluster.ports.  Two consumers: the RESIDUAL auction over the pods
    that lost round one, and CYCLE CHAINING — the serving loop reuses this
    as the next cycle's cluster instead of re-tensorizing the world
    (SURVEY §7 delta-updates; pad_pods_to/pad_terms_to pow2-pad the grown
    axes so successive cycles hit the same compiled programs)."""
    from .batch import densify_for
    from ..ops.selectors import pad_selector_slots
    batch = densify_for(cluster, batch)
    ext = _extend_cluster(cluster, batch)
    assigned = (chosen >= 0) & batch.valid
    ext = ext._replace(
        pod_node=jnp.concatenate([cluster.pod_node, chosen]),
        pod_valid=jnp.concatenate([cluster.pod_valid, assigned]),
        requested=requested,
        nonzero_requested=nz,
        ports=cluster.ports | (ports_used > 0.5),
    )
    if extend_score_terms:
        # a FRESH rebuild would put the newly-bound pods' score-side terms
        # into score_terms; chained clusters must match or scoring silently
        # diverges from a rebuild.  The same splice the auction makes for
        # its own later rounds (_extend_cluster, _splice_score_terms)
        ext = ext._replace(score_terms=_splice_score_terms(
            cluster, batch, cluster.pod_valid.shape[0],
            hard_pod_affinity_weight=hard_pod_affinity_weight))
    P = ext.pod_valid.shape[0]
    if pad_pods_to > P:
        n = pad_pods_to - P

        def padp(x, fill=0):
            pad = [(0, n)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, pad, constant_values=fill)
        ext = ext._replace(
            pod_kv=padp(ext.pod_kv), pod_key=padp(ext.pod_key),
            pod_ns_hot=padp(ext.pod_ns_hot),
            pod_node=padp(ext.pod_node, -1),
            pod_valid=padp(ext.pod_valid),
            pod_terminating=padp(ext.pod_terminating))
    ft = ext.filter_terms
    E = ft.valid.shape[0]
    if pad_terms_to > E:
        n = pad_terms_to - E

        def padt(x, fill=0):
            pad = [(0, n)] + [(0, 0)] * (x.ndim - 1)
            return jnp.pad(x, pad, constant_values=fill)
        ext = ext._replace(filter_terms=ft._replace(
            sel=pad_selector_slots(ft.sel, pad_terms_to),
            ns_hot=padt(ft.ns_hot), topo_key=padt(ft.topo_key),
            pod_idx=padt(ft.pod_idx), weight=padt(ft.weight),
            valid=padt(ft.valid)))
    return ext


def run_auction(cluster, batch, cfg: ProgramConfig, rng,
                host_ok=None, intra_batch_topology: bool = True,
                score_bias=None) -> GangResult:
    """The serving-loop gang entry: ONE device dispatch, ONE small readback.

    Round 3 ran a two-phase host-orchestrated residual auction here (full
    round, pull losers to host, re-auction a gathered pow2 bucket).  That
    traded device FLOPs for host round trips — the right trade when a
    full-batch round cost ~1.2 s of scatter-bound device time.  The
    same-pair MATMUL kernels made a full-matrix round cheap, and every
    intermediate device->host sync stalls the host on the device and the
    device on the host, so the monolithic while_loop (all rounds on
    device, zero intermediate syncs) IS the auction.  The two designs
    were last compared before PR 1; on current code and the v5e: not
    measured."""
    return schedule_gang(cluster, batch, cfg, rng, host_ok=host_ok,
                         intra_batch_topology=intra_batch_topology,
                         score_bias=score_bias)


def schedule_gang(cluster, batch, cfg: ProgramConfig, rng,
                  host_ok: Optional[jnp.ndarray] = None,
                  max_rounds: Optional[int] = None,
                  intra_batch_topology: bool = True,
                  tie_index: Optional[jnp.ndarray] = None,
                  residual_window: int = 512,
                  score_bias: Optional[jnp.ndarray] = None) -> GangResult:
    """Python entry for the jitted auction.  The indirection is a REQUIRED
    workaround for this runtime's jit dispatch: calling the jit object
    directly from multiple call sites with different static-arg
    combinations intermittently fails with 'Execution supplied N buffers
    but compiled program expected N+1' (argument-pruning bookkeeping
    crossing cache entries); routing every call through one Python frame
    avoids the C++ fastpath state that triggers it."""
    # the auction never samples nodes (it needs the global view), so
    # percentage_of_nodes_to_score must not split the program cache —
    # normalize it out of the static key
    if cfg.percentage_of_nodes_to_score != 100:
        cfg = cfg._replace(percentage_of_nodes_to_score=100)
    # AOT seam (utils/aot.py): armed, a signature hit runs the
    # deserialized build-time executable instead of tracing/compiling;
    # disarmed this is the plain jit call through the same Python frame
    from ..utils import aot
    return aot.dispatch(
        "_schedule_gang", _schedule_gang,
        (cluster, batch, cfg, rng),
        dict(host_ok=host_ok, max_rounds=max_rounds,
             intra_batch_topology=intra_batch_topology,
             tie_index=tie_index, residual_window=residual_window,
             score_bias=score_bias),
        static_argnums=(2,),
        static_argnames=("max_rounds", "intra_batch_topology",
                         "residual_window"))


# What a profiler trace calls the served auction: the lowered module is
# named for the jitted function ("jit__schedule_gang"), and the trace
# readers (perfbench/lib/readers.py AUCTION_PROGRAM) match this
# substring.  Rename the function and tests/test_program_names.py fails
# instead of the metric going silently empty.
AUCTION_PROGRAM = "schedule_gang"


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_rounds",
                                    "intra_batch_topology",
                                    "residual_window"))
def _schedule_gang(cluster, batch, cfg: ProgramConfig, rng,
                   host_ok: Optional[jnp.ndarray] = None,
                   max_rounds: Optional[int] = None,
                   intra_batch_topology: bool = True,
                   tie_index: Optional[jnp.ndarray] = None,
                   residual_window: int = 512,
                   score_bias: Optional[jnp.ndarray] = None) -> GangResult:
    return _gang_program(cluster, batch, cfg, rng, host_ok=host_ok,
                         max_rounds=max_rounds,
                         intra_batch_topology=intra_batch_topology,
                         tie_index=tie_index,
                         residual_window=residual_window,
                         score_bias=score_bias)


def _gang_program(cluster, batch, cfg: ProgramConfig, rng,
                  host_ok: Optional[jnp.ndarray] = None,
                  max_rounds: Optional[int] = None,
                  intra_batch_topology: bool = True,
                  tie_index: Optional[jnp.ndarray] = None,
                  residual_window: int = 512,
                  score_bias: Optional[jnp.ndarray] = None) -> GangResult:
    """The auction program body, jit-free: `_schedule_gang` above is its
    single-device jit root, and the shard_map mesh path
    (parallel/shardmap.py) traces the SAME body per device for its
    replicated topology surface — bit-identity across paths by
    construction, not by parallel maintenance."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    B = batch.req.shape[0]
    N = cluster.allocatable.shape[0]
    if max_rounds is None:
        max_rounds = B
    filters = set(cfg.filters)
    use_fit = "NodeResourcesFit" in filters
    use_ports = "NodePorts" in filters
    # Topology filters move into the round body (evaluated against committed
    # placements) when intra-batch topology is on; the host may pass
    # intra_batch_topology=False for batches it knows carry no pod-topology
    # terms, restoring the cheaper static evaluation.
    use_sph = "PodTopologySpread" in filters and intra_batch_topology
    use_ipa = "InterPodAffinity" in filters and intra_batch_topology
    intra = use_sph or use_ipa

    skip = ["NodeResourcesFit", "NodePorts"]
    if use_sph:
        skip.append("PodTopologySpread")
    if use_ipa:
        skip.append("InterPodAffinity")
    # Static filters once (everything the rounds don't re-evaluate);
    # Fit/Ports are not UnschedulableAndUnresolvable filters and
    # InterPodAffinity's unresolvable part is re-captured at round 0, so the
    # final unresolvable mask matches run_filters' full pass.
    static_ok, static_unres, affinity_ok = run_filters(
        cluster, batch, cfg, host_ok, skip=tuple(skip))
    base = cluster.node_valid[None, :] & batch.valid[:, None]
    if host_ok is not None:
        base = base & host_ok
    ports_ok0 = (K.node_ports_filter(cluster, batch) if use_ports
                 else jnp.ones((B, N), bool))

    ext = (_extend_cluster(cluster, batch, cfg.batch_score_sets,
                           cfg.hard_pod_affinity_weight)
           if intra else cluster)
    score_names = set(n for n, _ in cfg.scores)
    # assignment-independent raw scores: computed ONCE; only their
    # normalization (a [B, N] reduce over the evolving feasible mask)
    # stays in the round loop.  node_affinity_score alone re-ran a full
    # [B*Tp, L] x [N, L] selector match per round before this.
    from .programs import static_raw_scores
    score_pre = dict(static_raw_scores(ext, batch, cfg))
    # hoist every assignment-independent selector match out of the round
    # loop, one row a UNIQUE selector ([U, P]; all False, unmatched, for a
    # term set with no valid row — ops/kernels.py _if_live): only the
    # expansion to the round's pods and the segment/gather work that
    # depends on the carry's assignments run per round, and those only
    # for a live set.
    if "InterPodAffinity" in score_names:
        score_pre["interpod_score"] = K.interpod_score_pre(ext, batch)
    if "PodTopologySpread" in score_names:
        score_pre["spread_soft"] = K.spread_match_ns(ext, batch,
                                                     batch.spread_soft)
    if "DefaultPodTopologySpread" in score_names:
        score_pre["default_spread"] = K.default_spread_match_ns(ext, batch)
    if intra:
        # the topology keys the intra-round rules serialize on
        TK = cluster.topo_pair.shape[1]
        deferral_keys = (list(range(TK)) if not cfg.active_topo_keys else
                         [k for k in cfg.active_topo_keys if 0 <= k < TK])
        sph_match = (K.spread_match_ns(ext, batch, batch.spread)
                     if use_sph else None)
        ipa_pre = K.interpod_filter_pre(ext, batch) if use_ipa else None
    if use_ipa:
        has_ra = jnp.any(batch.ra.valid, axis=1)
        ra_boot = (jnp.all(batch.ra.self_match | ~batch.ra.valid, axis=1)
                   & has_ra)
        mu_raa = match_selectors_unique(batch.raa.sel, batch.kv_hot,
                                        batch.key_hot)  # [Ur, B]
        raa_uidx = jnp.asarray(batch.raa.sel.index).reshape(
            B, batch.raa.valid.shape[1])
    if use_sph:
        mu_sph = match_selectors_unique(batch.spread.sel, batch.kv_hot,
                                        batch.key_hot)  # [Us, B]
        sph_uidx = jnp.asarray(batch.spread.sel.index).reshape(
            B, batch.spread.valid.shape[1])

        def _sph_mixed():
            # a unique selector whose community (the pods it matches and
            # the pods whose valid constraints use it) spans namespaces:
            # mu_sph does not read the namespace, so this round's admits
            # counted by selector run high for such a one (spread_turns)
            Us = mu_sph.shape[0]
            NS = batch.ns_hot.shape[1]
            nsid = jnp.sum(jnp.where(batch.ns_hot > 0.5,
                                     jnp.arange(NS)[None, :], 0), axis=1)
            uses = jnp.any((sph_uidx[None, :, :]
                            == jnp.arange(Us)[:, None, None])
                           & batch.spread.valid[None, :, :], axis=2)
            member = (mu_sph | uses) & batch.valid[None, :]
            lo = jnp.min(jnp.where(member, nsid[None, :], 2**30), axis=1)
            hi = jnp.max(jnp.where(member, nsid[None, :], -1), axis=1)
            return hi > lo
        sph_mixed = K._if_live(
            jnp.any(batch.spread.valid), _sph_mixed,
            lambda: jnp.zeros((mu_sph.shape[0],), bool))

    # tie_index: each pod's selectHost RNG stream id (fold_in index).  The
    # residual auction passes the pods' ORIGINAL batch rows here so its
    # draws replay the monolithic loop's exactly.
    pod_idx = (jnp.arange(B, dtype=jnp.int32) if tie_index is None
               else jnp.asarray(tie_index, jnp.int32))
    tie_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(pod_idx)

    P = batch.ports_hot.shape[1]
    carry0 = dict(
        req=cluster.requested,
        nz=cluster.nonzero_requested,
        ports_used=jnp.zeros((N, P), jnp.float32),
        assigned=jnp.full((B,), -1, jnp.int32),
        win_score=jnp.zeros((B,), jnp.float32),
        feas0=jnp.zeros((B, N), bool),
        unres=static_unres,
        rounds=jnp.int32(0),
        # rounds that ADMITTED >= 1 pod: the windowed loop's budget.
        # Retire-only rounds must not consume it — with many permanently-
        # infeasible low-index pods the admit/retire alternation can take
        # far more than B total rounds while making real progress, and
        # charging those rounds against max_rounds starved still-feasible
        # pods into spurious preemption_may_help failures (ADVICE r5).
        # Admission rounds are intrinsically <= B (each assigns >= 1 pod),
        # so the budget keeps its original meaning.
        admits=jnp.int32(0),
        # proposals that found their node full at their turn (a hostPort
        # an earlier proposer registered counts as full), summed over the
        # rounds: diagnostics, GangResult.capacity_deferred
        cap_deferred=jnp.int32(0),
        progress=jnp.bool_(True),
        # windowed-residual bookkeeping: pods proven infeasible in a round
        # with no admission leave the selection pool until an admission
        # re-opens feasibility (see _round below)
        retired=jnp.zeros((B,), bool),
    )
    if use_ipa:
        # GangResult.affinity_bootstrap_admits
        carry0["boot_admits"] = jnp.int32(0)
    if use_sph:
        # the round proposes over the STRICT spread verdict (set by a
        # widened round that admitted nobody, for the one round after it)
        carry0["strict"] = jnp.bool_(False)
        carry0["late"] = jnp.int32(0)   # GangResult.spread_late_admits
        # times spread_turns held each pod back: such a pod draws its
        # tie-break anew (round_step)
        carry0["held"] = jnp.zeros((B,), jnp.int32)

    # ---- width-W views of every per-pod tensor the round math reads ----
    TERM_ROW_FIELDS = ("ns_hot", "topo_key", "topo_known", "weight",
                       "valid", "self_match", "max_skew")

    def _gather_terms(t, rsafe):
        """Row-gather a PodTerms/SpreadConstraints set: its dense [B, ...]
        companion arrays, and its SelectorSet's slot index (the unique
        compiled selectors are shared), so the in-round kernels expand
        the hoisted unique-selector matches (sph_match / ipa_pre /
        score_pre, [U, P]) over the window's own rows."""
        T = t.valid.shape[1]
        index = jnp.take(jnp.asarray(t.sel.index).reshape(B, T), rsafe,
                         axis=0).reshape(-1)
        return t._replace(sel=t.sel._replace(index=index),
                          **{f: jnp.take(getattr(t, f), rsafe, axis=0)
                             for f in TERM_ROW_FIELDS if f in t._fields})

    def full_sub():
        sb = dict(rows=jnp.arange(B, dtype=jnp.int32), valid=batch.valid,
                  batch=batch, static_ok=static_ok, ports_ok0=ports_ok0,
                  affinity_ok=affinity_ok, tie_keys=tie_keys,
                  score_pre=score_pre, score_bias=score_bias)
        if intra:
            sb["sph_match"] = sph_match
            sb["ipa_pre"] = ipa_pre
        if use_ipa:
            sb["ra_boot"] = ra_boot
            sb["mu_raa"] = mu_raa
            sb["raa_uidx"] = raa_uidx
        if use_sph:
            sb["mu_sph"] = mu_sph
            sb["sph_uidx"] = sph_uidx
            sb["sph_mixed"] = sph_mixed
        return sb

    def gather_sub(rows):
        rsafe = jnp.clip(rows, 0, B - 1)
        wvalid = rows < B

        def g(x):
            return jnp.take(x, rsafe, axis=0)

        def g_em(pre):
            # the hoisted matches are per UNIQUE selector ([U, P]) and
            # shared; the existing-terms match has a column a pod
            return pre._replace(em=pre.em[:, rsafe])

        sub_batch = batch._replace(
            req=g(batch.req), nonzero_req=g(batch.nonzero_req),
            ports_hot=g(batch.ports_hot),
            ports_asnode_hot=g(batch.ports_asnode_hot),
            ns_hot=g(batch.ns_hot),
            spread_selector=batch.spread_selector._replace(
                index=g(jnp.asarray(batch.spread_selector.index))),
            spread_skip=g(batch.spread_skip),
            valid=g(batch.valid) & wvalid,
            ra=_gather_terms(batch.ra, rsafe),
            raa=_gather_terms(batch.raa, rsafe),
            pref=_gather_terms(batch.pref, rsafe),
            spread=_gather_terms(batch.spread, rsafe),
            spread_soft=_gather_terms(batch.spread_soft, rsafe))
        sb = dict(rows=rows, valid=sub_batch.valid, batch=sub_batch,
                  static_ok=g(static_ok), ports_ok0=g(ports_ok0),
                  affinity_ok=g(affinity_ok), tie_keys=g(tie_keys),
                  score_pre={k: g_em(v) if k == "interpod_score"
                             else g(v) if k.startswith("raw:") else v
                             for k, v in score_pre.items()},
                  score_bias=None if score_bias is None
                  else g(score_bias))
        if intra:
            sb["sph_match"] = sph_match
            sb["ipa_pre"] = g_em(ipa_pre) if use_ipa else None
        if use_ipa:
            sb["ra_boot"] = g(ra_boot)
            sb["mu_raa"] = mu_raa[:, rsafe]
            sb["raa_uidx"] = g(raa_uidx)
        if use_sph:
            sb["mu_sph"] = mu_sph[:, rsafe]
            sb["sph_uidx"] = g(sph_uidx)
            sb["sph_mixed"] = sph_mixed
        return sb

    def cluster_at(c):
        """The cluster as this round sees it: committed resource usage, and
        (under intra) the batch's admitted pods live on the existing-pod
        axis at their assigned nodes."""
        cl = ext._replace(requested=c["req"], nonzero_requested=c["nz"])
        if intra:
            pod_node = jnp.concatenate([cluster.pod_node, c["assigned"]])
            pod_valid = jnp.concatenate(
                [cluster.pod_valid, (c["assigned"] >= 0) & batch.valid])
            cl = cl._replace(pod_node=pod_node, pod_valid=pod_valid)
        return cl

    def feasibility(c, cl, sb, n_open=None):
        """The round's feasible mask.  With the spread filter in the
        rounds it is the mask PROPOSALS run over: the filter's verdict
        widened by what the round's n_open unassigned pods can lift a
        constraint's minimum (K.spread_filter open_pods; 0 for a selector
        spread_turns cannot count exactly), or the strict one where the
        carry asks for it; sph_ok is the strict verdict either way."""
        feas = sb["static_ok"]
        sbatch = sb["batch"]
        aff_unres = None
        boot_live = None
        room = None
        sph_ok = None
        if use_sph:
            open_pods = jnp.where(
                jnp.take(sb["sph_mixed"], sb["sph_uidx"]), 0, n_open)
            sph_ok, room = K.spread_filter(cl, sbatch, sb["affinity_ok"],
                                           match_ns=sb["sph_match"],
                                           active_keys=cfg.active_keys,
                                           return_slack=True,
                                           open_pods=open_pods)
            feas = feas & jnp.where(c["strict"], sph_ok, room.ok_wide)
        if use_ipa:
            ok, aff_unres, boot_live = K.interpod_filter(
                cl, sbatch, pre=sb["ipa_pre"], return_no_matches=True,
                active_keys=cfg.active_keys)
            feas = feas & ok
        if use_fit:
            feas = feas & K.fit_filter(cl, sbatch)
        if use_ports:
            batch_conf = jnp.einsum(
                "bp,np->bn", sbatch.ports_hot, c["ports_used"],
                preferred_element_type=jnp.float32) > 0.5
            feas = feas & sb["ports_ok0"] & ~batch_conf
        return feas, aff_unres, boot_live, room, sph_ok

    def _rules_for(terms, mu, uidx, k, pair_ok, order, is_start, admit_cap):
        """Selector-precise same-pair deferral for the required
        anti-affinity terms x one key.  rule A: pod j defers iff an
        earlier-admitted pod in its landing pair matches one of j's key-k
        term selectors.  rule B: pod j defers iff it matches a key-k anti
        term of an earlier-admitted pod in the same pair."""
        W = admit_cap.shape[0]
        key_terms = _key_terms_mask(terms, k)  # [W, T]
        adm = _f(admit_cap & pair_ok)[:, None]
        # events A: admitted pods as selector members
        e_a = mu.T * adm                               # [W, U]
        pref_a = jnp.zeros_like(e_a).at[order].set(
            _seg_prefix(e_a[order], is_start))
        hits = jnp.take_along_axis(pref_a, uidx, axis=1) > 0  # [W, T]
        defer = jnp.any(hits & key_terms, axis=1) & pair_ok
        # events B: admitted pods registering their key-k selectors
        reg = jnp.zeros_like(e_a).at[
            jnp.arange(W)[:, None], uidx].max(_f(key_terms))
        e_b = reg * adm
        pref_b = jnp.zeros_like(e_b).at[order].set(
            _seg_prefix(e_b[order], is_start))
        return defer | (jnp.any((pref_b > 0) & mu.T, axis=1) & pair_ok)

    def topology_deferral(sb, admit_cap, prop, boot):
        """Selector-precise intra-round serialization of InterPodAffinity:
        see module docstring.  One stable sort by landing pair per
        topology key; the per-pair exclusive prefix sums run in
        unique-selector space (O(W x U) per key), so deferral only
        triggers on genuinely interacting pods — not on mere pair
        co-occupancy."""
        W = prop.shape[0]
        prop_safe = jnp.clip(prop, 0, N - 1)
        is_prop = prop < N
        defer = jnp.zeros((W,), bool)
        for k in deferral_keys:
            pair_k = jnp.where(is_prop, cluster.topo_pair[prop_safe, k], -1)
            pair_ok = pair_k >= 0
            skey = jnp.where(pair_ok, pair_k, jnp.int32(2**30))
            order = jnp.argsort(skey, stable=True)
            spair = skey[order]
            is_start = jnp.concatenate(
                [jnp.ones((1,), bool), spair[1:] != spair[:-1]])
            defer = defer | _rules_for(sb["batch"].raa, sb["mu_raa"],
                                       sb["raa_uidx"], k,
                                       pair_ok, order, is_start, admit_cap)
        # bootstrap rule: a pod whose required-affinity terms match
        # nothing THIS round is admitted only via the self-match
        # bootstrap (filtering.go:356); any same-round admission could
        # create a match and invalidate "no matches", so it defers
        # behind any earlier admission.  Once matches exist the normal
        # count path applies and co-admission is monotone-safe
        # (placements only add matches), so no deferral.
        earlier_any = jnp.cumsum(_f(admit_cap)) - _f(admit_cap)
        defer = defer | (boot & (earlier_any > 0))
        return defer

    def spread_turns(sb, cand, prop, room):
        """PodTopologySpread's hard filter at each pod's TURN (module
        docstring): cand [W] are the pods every other rule admits, in pod
        order; returns (admitted [W], late i32).  Pod j is admitted iff for
        each of its valid constraints c, with e the pods admitted BEFORE it
        in this round that c's selector matches in its proposal's pair and
        rise how far they lifted c's minimum, e - rise <= the room the
        filter saw at the round's start (slack at the proposal), which is
        matchNum + e + selfMatch - (minMatch + rise) <= maxSkew on this
        round's counts.  One serial pass over the pods that hold a valid
        constraint or match a constraint's selector (nobody else can be
        held back or use up room), its state the round's admits by
        (unique selector, key) on every node of their pair, [Us, K, N];
        no product.  late: the admitted whose round-start room was used
        up, or negative, at their turn."""
        sbatch = sb["batch"]
        cons = sbatch.spread
        W = prop.shape[0]

        def live():
            mu, uidx, mixed = sb["mu_sph"], sb["sph_uidx"], sb["sph_mixed"]
            Us = mu.shape[0]
            kk = jnp.asarray(deferral_keys, jnp.int32)                 # [K]
            on_key = cons.topo_key[:, :, None] == kk[None, None, :]
            kslot = jnp.argmax(on_key, axis=2)                     # [W, C]
            counted = (cons.valid & cons.topo_known
                       & jnp.any(on_key, axis=2))                  # [W, C]
            prop_safe = jnp.clip(prop, 0, N - 1)
            tpk = cluster.topo_pair[:, kk].T                       # [K, N]
            pair_at = jnp.where((prop < N)[:, None],
                                tpk[:, prop_safe].T, -1)           # [W, K]
            room_at = jnp.take_along_axis(
                room.slack, prop_safe[:, None, None], axis=2)[:, :, 0]
            min0 = jnp.min(room.floor, axis=2)                     # [W, C]
            exact = ~jnp.take(mixed, uidx)                         # [W, C]
            turns = cand & (jnp.any(counted, axis=1) | jnp.any(mu, axis=0))
            order = jnp.nonzero(turns, size=W, fill_value=0)[0]

            def turn(i, st):
                landed, admitted, late = st
                j = order[i]
                node = prop_safe[j]
                rows = landed[uidx[j], kslot[j]]                   # [C, N]
                floor = room.floor[j]                              # [C, N]
                rise = jnp.min(floor + rows * _f(exact[j])[:, None],
                               axis=1) - min0[j]
                # an unregistered pair counts no pod (Filter: nil tpCount)
                e = jnp.where(floor[:, node] < K._UNREGISTERED,
                              rows[:, node], 0.0)
                ok = ~jnp.any(counted[j] & (e - rise > room_at[j]))
                was_late = ok & jnp.any(counted[j] & (e > room_at[j]))
                same = ((tpk == pair_at[j][:, None])
                        & (pair_at[j] >= 0)[:, None])              # [K, N]
                landed = landed + (_f(mu[:, j] & ok)[:, None, None]
                                   * _f(same)[None, :, :])
                return (landed, admitted.at[j].set(ok),
                        late + was_late.astype(jnp.int32))

            st0 = (jnp.zeros((Us, len(deferral_keys), N), jnp.float32),
                   cand & ~turns, jnp.int32(0))
            _, admitted, late = jax.lax.fori_loop(
                0, jnp.sum(turns, dtype=jnp.int32), turn, st0)
            return admitted, late

        with jax.named_scope("spread_turns"):
            return K._if_live(jnp.any(cons.valid), live,
                              lambda: (cand, jnp.int32(0)))

    def round_step(c, sb, capture_first: bool, windowed: bool = False):
        """One propose/admit round over sb's rows (width W <= B; the full
        round passes identity rows).  Updates the full-width carry through
        mode='drop' scatters, so sentinel rows (>= B) are no-ops."""
        rows = sb["rows"]
        rsafe = jnp.clip(rows, 0, B - 1)
        sbatch = sb["batch"]
        unassigned = (jnp.take(c["assigned"], rsafe) < 0) & sb["valid"]
        cl = cluster_at(c)
        feas, aff_unres, boot_live, room, sph_ok = feasibility(
            c, cl, sb,
            jnp.sum(unassigned, dtype=jnp.int32) if use_sph else None)
        feas = feas & unassigned[:, None]

        # scores against committed usage + placements so later rounds see
        # earlier rounds' pods (the batched analog of assume-before-next-pod)
        scores, _ = run_scores(cl, sbatch, cfg, feas, sb["affinity_ok"],
                               pre=sb["score_pre"])
        if sb.get("score_bias") is not None:
            # weighted host Score/NormalizeScore plugin totals, computed by
            # the framework runner pre-dispatch (framework.go:579-656)
            scores = scores + sb["score_bias"]

        masked = jnp.where(feas, scores, _NEG)
        best = jnp.max(masked, axis=1)
        ties = (masked == best[:, None]) & feas
        logits = jnp.where(ties, 0.0, _NEG)
        tie_keys = sb["tie_keys"]
        if use_sph:
            # a pod spread_turns held back proposed a node whose pair was
            # full at its turn; its own stream would draw that node again
            # while it stays in the tie set, and the pods a widened round
            # leaves over would all be such (the ones whose favourite's
            # pair is the full one).  It draws from the next stream
            held = jnp.take(c["held"], rsafe)
            tie_keys = jnp.where(
                (held > 0).reshape((-1,) + (1,) * (tie_keys.ndim - 1)),
                jax.vmap(jax.random.fold_in)(tie_keys, held), tie_keys)
        choice = jax.vmap(jax.random.categorical)(tie_keys, logits)
        active = jnp.any(feas, axis=1)
        prop = jnp.where(active, choice.astype(jnp.int32), N)  # N = no-op seg
        # round 0's capture (n_feasible, the preemption gate) is the
        # filters' own verdict: the strict one
        feas0 = feas if sph_ok is None else feas & sph_ok
        return _round_tail(c, sb, prop, active, best, unassigned,
                           windowed=windowed, capture_first=capture_first,
                           feas=feas0, aff_unres=aff_unres,
                           boot_live=boot_live, room=room)

    def _round_tail(c, sb, prop, active, best, unassigned,
                    windowed: bool, capture_first: bool = False,
                    feas=None, aff_unres=None, boot_live=None, room=None):
        """The admit/commit half of a round: segmented-reduce admission
        over the proposed nodes + carry update.  O(W) / O(W, R) work."""
        rows = sb["rows"]
        rsafe = jnp.clip(rows, 0, B - 1)
        sbatch = sb["batch"]

        # ---- admission: sort by proposed node (stable keeps pod order;
        # rows are ascending original indices, so sub-round order == the
        # full round's order restricted to these pods) ----
        admit = admission_mask(prop, active, sbatch.req, sbatch.ports_hot,
                               sbatch.ports_asnode_hot, cluster.allocatable,
                               c["req"], use_ports, N)
        cap_deferred = jnp.sum(active & ~admit, dtype=jnp.int32)
        if use_ipa:
            # intra-round topology serialization (conservative; deferred
            # pods re-check against exact committed counts next round)
            # the pods only the self-match bootstrap lets in this round
            boot = sb["ra_boot"] & boot_live
            admit = admit & ~topology_deferral(sb, admit, prop, boot)
        if use_sph:
            # last, on what every other rule admits: a pod held back must
            # lift nobody's minimum
            cand = admit
            admit, late = spread_turns(sb, cand, prop, room)

        # ---- commit ----
        add_req, add_nz, add_ports = admission_sums(
            admit, prop, sbatch.req, sbatch.nonzero_req,
            sbatch.ports_asnode_hot, use_ports, N)
        new = dict(c)
        new["req"] = c["req"] + add_req
        new["nz"] = c["nz"] + add_nz
        if use_ports:
            new["ports_used"] = jnp.maximum(c["ports_used"], add_ports)
        new["assigned"] = c["assigned"].at[rows].set(
            jnp.where(admit, prop, jnp.take(c["assigned"], rsafe)),
            mode="drop")
        new["win_score"] = c["win_score"].at[rows].set(
            jnp.where(admit, best, jnp.take(c["win_score"], rsafe)),
            mode="drop")
        if capture_first:
            new["feas0"] = jnp.where(c["rounds"] == 0, feas, c["feas0"])
            if aff_unres is not None:
                new["unres"] = jnp.where(c["rounds"] == 0,
                                         c["unres"] | (aff_unres & base),
                                         c["unres"])
        admitted_any = jnp.any(admit)
        new["rounds"] = c["rounds"] + 1
        new["admits"] = c["admits"] + admitted_any.astype(jnp.int32)
        new["cap_deferred"] = c["cap_deferred"] + cap_deferred
        if use_ipa:
            new["boot_admits"] = c["boot_admits"] + jnp.sum(
                admit & boot, dtype=jnp.int32)
        # did this round propose past the strict spread verdict?  Such a
        # round proves nothing by admitting nobody: the next one is strict
        # (its first proposer always fits), and only a strict round's
        # emptiness retires a pod or ends the loop
        progressed = admitted_any
        if use_sph:
            widened = ~c["strict"] & jnp.any(room.widened & unassigned)
            new["strict"] = widened & ~admitted_any
            new["late"] = c["late"] + late
            new["held"] = c["held"].at[rows].add(
                (cand & ~admit).astype(jnp.int32), mode="drop")
            progressed = admitted_any | widened
        if windowed:
            # retirement: a pod with NO feasible node in a no-admission
            # round leaves the window-selection pool; any admission
            # re-opens everyone's feasibility (affinity matches only
            # accumulate), so the pool resets.  This keeps windowed rounds
            # live: unschedulable pods at the head of the pool cannot pin
            # the window forever.  Only FIRST-TIME retirements count as
            # progress, or an all-unschedulable tail would re-retire
            # forever and burn max_rounds.
            new_retire = ((~active) & unassigned
                          & ~jnp.take(c["retired"], rsafe))
            if use_sph:
                new_retire = new_retire & ~widened
            new["retired"] = jnp.where(
                admitted_any, jnp.zeros_like(c["retired"]),
                c["retired"].at[rows].max(new_retire, mode="drop"))
            new["progress"] = progressed | jnp.any(new_retire)
        else:
            new["progress"] = progressed
        return new

    fsb = full_sub()
    use_window = bool(residual_window) and residual_window < B  # kubelint: ignore[host-sync/cast] trace-time constant: residual_window is a static int (jit static_argnames on _schedule_gang)

    if not use_window:
        def cond(c):
            return c["progress"] & (c["rounds"] < max_rounds)

        def body(c):
            return round_step(c, fsb, capture_first=True)

        out = jax.lax.while_loop(cond, body, carry0)
    elif max_rounds < 1:
        out = carry0
    else:
        # phase A: one full-width round admits the uncontended bulk and
        # captures feas0/unres; phase B loops over a residual WINDOW of the
        # first residual_window still-unassigned pods — the same round math
        # at ~W/B the FLOPs, since every in-round tensor row-gathers to W.
        out = round_step(carry0, fsb, capture_first=True, windowed=True)

        def condw(c):
            # budget on ADMISSION rounds, not total rounds: retire-only
            # rounds are free (progress still gates them — a round that
            # neither admits nor newly retires ends the loop), so feasible
            # pods behind a long infeasible tail cannot be starved by the
            # admit/retire alternation burning the shared budget
            pool = (c["assigned"] < 0) & batch.valid & ~c["retired"]
            return (c["progress"] & jnp.any(pool)
                    & (c["admits"] < max_rounds))

        def bodyw(c):
            pool = (c["assigned"] < 0) & batch.valid & ~c["retired"]
            rows = jnp.nonzero(pool, size=residual_window,
                               fill_value=B)[0].astype(jnp.int32)
            return round_step(c, gather_sub(rows), capture_first=False,
                              windowed=True)

        out = jax.lax.while_loop(condw, bodyw, out)
    unresolvable = out["unres"]
    # the preemption gate must see HOST-filter failures as resolvable
    # (nodesWherePreemptionMightHelp counts them;
    # preemption.Preemptor._wave_candidates re-checks them), so
    # host_ok is deliberately NOT part of this node-exclusion mask
    base_nodes = cluster.node_valid[None, :] & batch.valid[:, None]
    all_unres = jnp.all(unresolvable | out["feas0"] | ~base_nodes, axis=1)
    n_feas = jnp.sum(out["feas0"].astype(jnp.int32), axis=1)
    packed = jnp.concatenate([out["assigned"], n_feas,
                              all_unres.astype(jnp.int32),
                              out["rounds"].reshape(1)])
    soft_skew = (K.spread_soft_skew(cluster_at(out), batch,
                                    match_ns=score_pre["spread_soft"])
                 if "PodTopologySpread" in score_names else jnp.int32(-1))
    return GangResult(chosen=out["assigned"], score=out["win_score"],
                      rounds=out["rounds"], requested=out["req"],
                      nz=out["nz"], ports_used=out["ports_used"],
                      feasible0=out["feas0"], unresolvable=unresolvable,
                      n_feasible=n_feas,
                      all_unresolvable=all_unres, packed=packed,
                      capacity_deferred=out["cap_deferred"],
                      soft_spread_skew=soft_skew,
                      spread_late_admits=out.get("late"),
                      affinity_bootstrap_admits=out.get("boot_admits"))
