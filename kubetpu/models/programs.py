"""Composed scheduling programs: the device-side replacement for the
reference's per-pod Filter -> Score -> NormalizeScore -> weight -> selectHost
pipeline (reference: pkg/scheduler/core/generic_scheduler.go:146 Schedule,
prioritizeNodes :622, selectHost :217; weight application
framework/v1alpha1/framework.go:579-656).

A ScheduleProgram is configured with a static plugin set + weights (one per
scheduler profile) and jit-compiles one XLA program that filters and scores a
whole batch of B pods against N nodes at once.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..ops import kernels as K

# Sharded and single-device dispatch must pick IDENTICAL placements: the
# selectHost tie-break samples random bits, and under the legacy
# (non-partitionable) threefry lowering those bits change when the logits
# are sharded over a mesh — silently breaking the serial-replay oracle for
# multi-chip runs.  Partitionable threefry makes the bits a pure function
# of key + position at every sharding (newer jax defaults to exactly this;
# pinning it here keeps placements stable across jax versions too).
jax.config.update("jax_threefry_partitionable", True)

# Default plugin weights (reference: algorithmprovider/registry.go:119-134).
DEFAULT_SCORE_PLUGINS: Tuple[Tuple[str, int], ...] = (
    ("NodeResourcesBalancedAllocation", 1),
    ("ImageLocality", 1),
    ("InterPodAffinity", 1),
    ("NodeResourcesLeastAllocated", 1),
    ("NodeAffinity", 1),
    ("NodePreferAvoidPods", 10000),
    ("PodTopologySpread", 2),
    ("DefaultPodTopologySpread", 1),
    ("TaintToleration", 1),
)

DEFAULT_FILTER_PLUGINS: Tuple[str, ...] = (
    "NodeUnschedulable",
    "NodeResourcesFit",
    "NodeName",
    "NodePorts",
    "NodeAffinity",
    "TaintToleration",
    "PodTopologySpread",
    "InterPodAffinity",
)

# Filters whose failure is UnschedulableAndUnresolvable — preemption cannot
# help on such nodes (reference: status codes per plugin; consumed by
# nodesWherePreemptionMightHelp, core/generic_scheduler.go:1041).
UNRESOLVABLE_FILTERS = frozenset({
    "NodeUnschedulable", "NodeName", "NodeAffinity", "TaintToleration",
    "NodeLabel",  # nodelabel/node_label.go:106 ErrReasonPresenceViolated
})


class ProgramConfig(NamedTuple):
    """Static (hashable) program configuration — one per profile."""
    filters: Tuple[str, ...] = DEFAULT_FILTER_PLUGINS
    scores: Tuple[Tuple[str, int], ...] = DEFAULT_SCORE_PLUGINS
    hostname_topokey: int = 0  # topokey vocab id of kubernetes.io/hostname
    # per-plugin static kernel args, e.g. RequestedToCapacityRatio's shape
    # or NodeLabel's resolved key ids: ((plugin, args-tuple), ...)
    plugin_args: Tuple[Tuple[str, Tuple], ...] = ()
    # adaptive node-sampling percentage for the sequential replay
    # (reference: percentageOfNodesToScore, generic_scheduler.go:54-59,
    # 379-399).  100 = search every node (the unit-test/kernel default);
    # 0 = the reference's adaptive default 50 - n/125, floor 5%; the
    # sampled search only ever binds on clusters >= 100 nodes.
    percentage_of_nodes_to_score: int = 100
    # topology-key ids that can appear in the BATCH's term sets (affinity,
    # anti-affinity, preferred, spread constraints).  The same-pair matmul
    # kernels run one [S, .] x [., N] contraction per key; restricting to
    # the keys actually present (typically 2 of the TK=8 seeded keys) cuts
    # that work proportionally.  () = unknown -> all keys (always safe);
    # a non-empty tuple MUST be a superset of the batch's term keys.
    active_topo_keys: Tuple[int, ...] = ()
    # the BATCH's own term sets that score OTHER pods once their owner is
    # bound: "pref" (preferred affinity and anti-affinity, signed weights),
    # "ra" (required affinity, at hard_pod_affinity_weight).  The gang
    # auction splices the named sets into score_terms, so a pod admitted in
    # round r scores the pods of later rounds as a bound pod would
    # (models/gang.py _extend_cluster).  Read off the batch by the
    # scheduler like active_topo_keys; () = the batch carries none and the
    # auction appends no row.  Naming a set the batch has no valid row of
    # is safe (its rows are invalid), naming too few under-counts.
    batch_score_sets: Tuple[str, ...] = ()
    # InterPodAffinityArgs.hardPodAffinityWeight, for the "ra" rows above
    # (existing pods' rows carry it in cluster.score_terms already)
    hard_pod_affinity_weight: float = 1.0

    @property
    def active_keys(self):
        return self.active_topo_keys or None

    def arg(self, name: str, default=()):
        for n, a in self.plugin_args:
            if n == name:
                return a
        return default


class FilterScoreResult(NamedTuple):
    feasible: jnp.ndarray       # [B, N] bool
    unresolvable: jnp.ndarray   # [B, N] bool (failed beyond preemption help)
    scores: jnp.ndarray         # [B, N] f32 weighted total (0 where infeasible)
    plugin_scores: Dict[str, jnp.ndarray]  # per-plugin weighted [B, N]


def _filter_mask(name: str, cluster, batch, cfg: ProgramConfig, affinity_ok):
    """One filter plugin's pass mask [B, N]; returns (ok, extra_unresolvable
    or None)."""
    if name == "NodeUnschedulable":
        return K.node_unschedulable_filter(cluster, batch), None
    if name == "NodeResourcesFit":
        return K.fit_filter(cluster, batch), None
    if name == "NodeName":
        return K.node_name_filter(cluster, batch), None
    if name == "NodePorts":
        return K.node_ports_filter(cluster, batch), None
    if name == "NodeAffinity":
        return affinity_ok, None
    if name == "TaintToleration":
        return K.taint_filter(cluster, batch), None
    if name == "PodTopologySpread":
        return K.spread_filter(cluster, batch, affinity_ok,
                               active_keys=cfg.active_keys), None
    if name == "InterPodAffinity":
        ok, aff_unres = K.interpod_filter(cluster, batch,
                                          active_keys=cfg.active_keys)
        return ok, aff_unres
    if name == "NodeLabel":
        present, absent, _ = cfg.arg("NodeLabel", ((), (), ()))
        return K.node_label_filter(cluster, batch, present, absent), None
    raise ValueError(f"unknown filter kernel {name}")


def run_filters(cluster, batch, cfg: ProgramConfig, host_ok=None,
                skip: Tuple[str, ...] = ()):
    """Returns (feasible, unresolvable, node_affinity_ok).  host_ok [B, N]
    carries the verdicts of host-side (non-tensorized) filter plugins —
    volumes, out-of-tree — computed by the framework runner and ANDed in
    here so device and host plugins share one feasibility mask.  skip names
    filters the caller evaluates itself (e.g. gang mode re-evaluates
    NodeResourcesFit/NodePorts against in-flight batch placements)."""
    base = cluster.node_valid[None, :] & batch.valid[:, None]
    if host_ok is not None:
        base = base & host_ok
    feasible = base
    unresolvable = jnp.zeros_like(base)
    affinity_ok = K.node_affinity_filter(cluster, batch)

    for name in cfg.filters:
        if name in skip:
            continue
        ok, extra_unres = _filter_mask(name, cluster, batch, cfg, affinity_ok)
        if extra_unres is not None:
            unresolvable = unresolvable | (extra_unres & base)
        if name in UNRESOLVABLE_FILTERS:
            unresolvable = unresolvable | (~ok & base)
        feasible = feasible & ok
    return feasible, unresolvable, affinity_ok


@functools.partial(jax.jit, static_argnames=("cfg",))
def explain_filters(cluster, batch, cfg: ProgramConfig, host_ok=None):
    """Per-filter unschedulability attribution for diagnostics/benchmarks
    (the tensor analog of the reference's per-node FailedPredicates map,
    core/generic_scheduler.go:565 podPassesFiltersOnNode status collection).

    For every pod with no feasible node, a filter is *blocking* when every
    node that passes all OTHER filters fails it.  Returns (no_feasible [B]
    bool, blocking [F, B] bool) with F = len(cfg.filters), evaluated against
    this snapshot."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    base = cluster.node_valid[None, :] & batch.valid[:, None]
    if host_ok is not None:
        base = base & host_ok
    affinity_ok = K.node_affinity_filter(cluster, batch)
    masks = [
        _filter_mask(name, cluster, batch, cfg, affinity_ok)[0] & base
        for name in cfg.filters]
    all_ok = base
    for m in masks:
        all_ok = all_ok & m
    no_feasible = ~jnp.any(all_ok, axis=1) & batch.valid
    blocking = []
    for i in range(len(masks)):
        others = base
        for j, m in enumerate(masks):
            if j != i:
                others = others & m
        blocked = jnp.any(others, axis=1) & ~jnp.any(others & masks[i], axis=1)
        blocking.append(blocked & no_feasible)
    return no_feasible, jnp.stack(blocking)


# best_score is shipped in integer MILLI-units so the whole audit packs
# into ONE i32 array (one device->host readback); the host divides back.
# Milli, not micro: default-profile totals reach ~1e6 per node
# (NodePreferAvoidPods weight 10000 x MAX_NODE_SCORE 100), which already
# overflows i32 at micro scale — and the cast clips as a second fence.
SCORE_SCALE = 1_000
_SCORE_I32_MAX = float(2**31 - 128)


def explain_verdicts(cluster, batch, cfg: ProgramConfig, host_ok=None):
    """Python entry for the jitted audit program — AOT seam (utils/aot.py):
    armed, a signature hit runs the deserialized build-time executable;
    disarmed this is the plain jit call.  See _explain_verdicts for the
    program itself."""
    from ..utils import aot
    return aot.dispatch(
        "_explain_verdicts", _explain_verdicts,
        (cluster, batch, cfg), dict(host_ok=host_ok),
        static_argnums=(2,))


# the served programs' names in a profiler trace / the compile watchdog:
# substrings of the lowered modules' names, pinned by
# tests/test_program_names.py (see models/gang.py AUCTION_PROGRAM)
EXPLAIN_PROGRAM = "explain_verdicts"
WHATIF_PROGRAM = "whatif_wave"
DELTA_PROGRAM = "apply_cluster_delta"
TERMS_DELTA_PROGRAM = "apply_terms_delta"


@functools.partial(jax.jit, static_argnames=("cfg",))
def _explain_verdicts(cluster, batch, cfg: ProgramConfig, host_ok=None):
    """The per-pod decision audit program (DecisionLog feed): everything
    the host needs to say WHY a pod was (un)schedulable this cycle, in
    ONE packed [2F + 3, B] i32 readback (F = len(cfg.filters)):

      rows 0..F-1      per-filter FAILED-NODE counts ("412 nodes failed
                       NodeResourcesFit") over valid nodes passing host_ok
      rows F..2F-1     0/1 blocking flags (explain_filters semantics: every
                       node passing all OTHER filters fails this one)
      row 2F           0/1 no-feasible-node flag
      row 2F + 1       best feasible node row (-1 when none) — argmax of
                       the weighted score over the feasible mask
      row 2F + 2       best feasible score in milli-units (SCORE_SCALE,
                       clipped to the i32 range)

    Evaluated against the cycle-start snapshot (same state the dispatch
    filtered), so a gang pod that lost purely to intra-batch contention
    reports its round-0 feasible count and best score."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    base = cluster.node_valid[None, :] & batch.valid[:, None]
    if host_ok is not None:
        base = base & host_ok
    affinity_ok = K.node_affinity_filter(cluster, batch)
    masks = [
        _filter_mask(name, cluster, batch, cfg, affinity_ok)[0] & base
        for name in cfg.filters]
    all_ok = base
    for m in masks:
        all_ok = all_ok & m
    no_feasible = ~jnp.any(all_ok, axis=1) & batch.valid
    fail_counts = [jnp.sum((base & ~m).astype(jnp.int32), axis=1)
                   for m in masks]
    blocking = []
    for i in range(len(masks)):
        others = base
        for j, m in enumerate(masks):
            if j != i:
                others = others & m
        blocked = jnp.any(others, axis=1) & ~jnp.any(others & masks[i], axis=1)
        blocking.append((blocked & no_feasible).astype(jnp.int32))
    scores, _ = run_scores(cluster, batch, cfg, all_ok, affinity_ok)
    neg = jnp.float32(-2**30)
    masked = jnp.where(all_ok, scores, neg)
    any_ok = jnp.any(all_ok, axis=1)
    best_node = jnp.where(any_ok, jnp.argmax(masked, axis=1), -1)
    best_score = jnp.where(any_ok, jnp.max(masked, axis=1), 0.0)
    return jnp.stack(fail_counts + blocking + [
        no_feasible.astype(jnp.int32),
        best_node.astype(jnp.int32),
        jnp.clip(jnp.round(best_score * SCORE_SCALE),
                 -_SCORE_I32_MAX, _SCORE_I32_MAX).astype(jnp.int32)])


STATIC_RAW_SCORES = {
    # score plugins whose RAW scores are independent of the auction carry
    # (requested usage and intra-batch placements): gang mode computes them
    # once and re-normalizes per round against the evolving feasible mask
    "ImageLocality": K.image_locality_score,
    "NodeAffinity": K.node_affinity_score,
    "NodePreferAvoidPods": K.prefer_avoid_pods_score,
    "TaintToleration": K.taint_toleration_score,
}


def static_raw_scores(cluster, batch, cfg: ProgramConfig):
    """Precompute the assignment-independent raw scores for run_scores'
    pre dict (keyed "raw:<plugin>")."""
    return {f"raw:{name}": fn(cluster, batch)
            for name, fn in STATIC_RAW_SCORES.items()
            if any(n == name for n, _ in cfg.scores)}


def run_scores(cluster, batch, cfg: ProgramConfig, feasible, affinity_ok,
               pre=None):
    """Per-plugin normalized scores x weight, summed
    (reference: framework.go:579-656 RunScorePlugins).  pre: optional dict
    of precomputed assignment-independent match tensors (gang mode hoists
    them out of its round loop): keys "interpod_score", "spread_soft",
    "default_spread", and "raw:<plugin>" raw-score arrays from
    static_raw_scores."""
    pre = pre or {}
    total = jnp.zeros(feasible.shape, jnp.float32)
    per_plugin: Dict[str, jnp.ndarray] = {}
    for name, weight in cfg.scores:
        if name == "NodeResourcesBalancedAllocation":
            s = K.balanced_allocation_score(cluster, batch)
        elif name == "ImageLocality":
            s = pre.get("raw:ImageLocality")
            if s is None:
                s = K.image_locality_score(cluster, batch)
        elif name == "InterPodAffinity":
            s = K.interpod_score(cluster, batch, feasible,
                                 pre=pre.get("interpod_score"),
                                 active_keys=cfg.active_keys)
        elif name == "NodeResourcesLeastAllocated":
            s = K.least_allocated_score(cluster, batch)
        elif name == "NodeResourcesMostAllocated":
            s = K.most_allocated_score(cluster, batch)
        elif name == "NodeAffinity":
            raw = pre.get("raw:NodeAffinity")
            if raw is None:
                raw = K.node_affinity_score(cluster, batch)
            s = K.default_normalize(raw, feasible, reverse=False)
        elif name == "NodePreferAvoidPods":
            s = pre.get("raw:NodePreferAvoidPods")
            if s is None:
                s = K.prefer_avoid_pods_score(cluster, batch)
        elif name == "PodTopologySpread":
            s = K.spread_soft_score(cluster, batch, feasible, affinity_ok,
                                    cfg.hostname_topokey,
                                    match_ns=pre.get("spread_soft"),
                                    active_keys=cfg.active_keys)
        elif name == "DefaultPodTopologySpread":
            raw = K.default_spread_score(cluster, batch,
                                         match_ns=pre.get("default_spread"))
            s = K.default_spread_normalize(cluster, batch, raw, feasible)
        elif name == "TaintToleration":
            raw = pre.get("raw:TaintToleration")
            if raw is None:
                raw = K.taint_toleration_score(cluster, batch)
            s = K.default_normalize(raw, feasible, reverse=True)
        elif name == "RequestedToCapacityRatio":
            # default shape already on the MaxNodeScore scale (the plugin
            # rescales config scores x10 at construction, see intree.py)
            shape, resources = cfg.arg(
                "RequestedToCapacityRatio",
                (((0, 0), (100, 100)), ((0, 0, 1), (1, 0, 1))))
            s = K.requested_to_capacity_ratio_score(cluster, batch, shape,
                                                    resources)
        elif name == "NodeResourceLimits":
            s = K.resource_limits_score(cluster, batch)
        elif name == "NodeLabel":
            _, _, prefs = cfg.arg("NodeLabel", ((), (), ()))
            s = K.node_label_score(cluster, batch, prefs)
        else:
            raise ValueError(f"unknown score kernel {name}")
        s = jnp.where(feasible, s, 0.0) * float(weight)  # kubelint: ignore[host-sync/cast] trace-time constant: weight is a static int from cfg.scores (jit static arg)
        per_plugin[name] = s
        total = total + s
    return total, per_plugin


@functools.partial(jax.jit, static_argnames=("cfg",))
def filter_verdicts(cluster, batch, cfg: ProgramConfig, host_ok=None):
    """Filters only — (feasible, unresolvable).  Preemption's shared
    verdict refresh uses this; computing scores there would be pure
    waste."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    feasible, unresolvable, _ = run_filters(cluster, batch, cfg, host_ok)
    return feasible, unresolvable


@functools.partial(jax.jit, static_argnames=("cfg",))
def whatif_static_ok(cluster, batch, cfg: ProgramConfig):
    """Per-(pod, node) verdict of every filter EXCEPT NodeResourcesFit —
    the victim-removal-invariant half of the preemption what-if.  Removing
    victims only perturbs the resource channels (requested/pod-count; the
    serial what-if never restores ports either) and, for term-carrying
    pods, the topology one-hots; callers route term-carrying pods to the
    per-pod reprieve instead (see preemption.py), so for wave pods this
    verdict is constant across the whole reprieve scan and one [B, N]
    pass covers every reprieve step of every candidate.  cfg must already
    have the droppable topology filters removed."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    feasible, _, _ = run_filters(cluster, batch, cfg,
                                 skip=("NodeResourcesFit",))
    return feasible


@jax.jit
def whatif_wave(cluster, static_ok, wave_req, cand_rows, cand_valid,
                nom_add, tab_req, tab_valid, cand_idx):
    """Wave-batched selectVictimsOnNode (generic_scheduler.go:949) for a
    whole cycle's failed pods at once — the [B, C, K] axis of the
    preemption wave (preemption.py preempt_wave).  All shape axes are
    pow2-bucketed by the caller (pow2_bucket) so repeated waves of similar
    size hit one compiled program.

    Victim tensors arrive as a compact per-(priority, node) TABLE plus
    per-(pod, candidate) indices into it — same-priority preemptors share
    victim rows, so the host->device transfer is O(S * K) instead of
    O(B * C * K) (the [B, C, K, R] expansion happens on device, in HBM).

    static_ok [B, N]      all non-fit filter verdicts (whatif_static_ok)
    wave_req  [B, R]      preemptor resource request channels
    cand_rows [B, C]      candidate node rows per pod (-1 pad)
    cand_valid [B, C]     real (pod, candidate) pairs
    nom_add   [B, C, R]   nominated-pod requests reserved on each candidate
                          (equal/higher priority, self excluded — the
                          addNominatedPods overlay, :594)
    tab_req   [S, K, R]   victim resources per table row, reprieve order
    tab_valid [S, K]      real victim slots per table row
    cand_idx  [B, C]      table row per (pod, candidate) (0 pad, masked by
                          cand_valid)

    Returns packed [B, C, K+1] bool: [..., 0] = pod fits with every victim
    removed (fits0); [..., 1 + k] = victim k was reprieved (stays)."""
    import jax.numpy as jnp

    rows = jnp.clip(cand_rows, 0)
    sok = jnp.take_along_axis(static_ok, rows, axis=1) & cand_valid  # [B, C]
    vic_req = jnp.take(tab_req, cand_idx, axis=0)           # [B, C, K, R]
    vic_valid = (jnp.take(tab_valid, cand_idx, axis=0)
                 & cand_valid[:, :, None])                  # [B, C, K]
    rm_req = jnp.sum(vic_req * vic_valid[..., None].astype(vic_req.dtype),
                     axis=2)                                # [B, C, R]
    free_base = jnp.take(cluster.allocatable - cluster.requested, rows,
                         axis=0)                            # [B, C, R]
    breq = jnp.broadcast_to(wave_req[:, None, :], free_base.shape)
    free = free_base - nom_add + rm_req
    fits0 = K.fit_rows(breq, free) & sok

    def step(carry, xs):
        free, ok = carry
        vreq, vvalid = xs                                   # [B,C,R],[B,C]
        exists = vvalid & ok
        try_free = free - vreq * exists[..., None].astype(free.dtype)
        fit = K.fit_rows(breq, try_free) & sok & exists
        free = jnp.where(fit[..., None], try_free, free)
        return (free, ok), fit

    (_, _), reprieved = jax.lax.scan(
        step, (free, fits0),
        (jnp.moveaxis(vic_req, 2, 0), jnp.moveaxis(vic_valid, 2, 0)))
    return jnp.concatenate(
        [fits0[:, :, None], jnp.moveaxis(reprieved, 0, -1)], axis=2)


def _apply_cluster_delta(cluster, delta):
    """Scatter one cycle's ClusterDelta tables (state/tensors.py) into the
    device-resident ClusterTensors.  Row vectors are padded with
    one-past-capacity indices, so ``mode="drop"`` discards the pads (a -1
    pad would WRAP to the last row); duplicate REAL rows never occur (the
    host dedups dirty rows before gathering).  The compact label-id lists
    densify on device exactly like HostClusterArrays.to_device, so a
    delta-applied cluster stays byte-identical to a rebuild."""
    from ..state.tensors import _densify_ids
    from ..utils.intern import pow2_bucket

    nr, pr = delta.node_rows, delta.pod_rows
    # kv width is always an InternTable .cap (pow2_bucket of the vocab, so a
    # power of two >= 8) — re-bucketing is identity at runtime and proves to
    # the closure engine that the static L of _densify_ids stays on the
    # pow2 ladder.
    L = pow2_bucket(cluster.kv.shape[1])

    def scat(x, rows, vals):
        return x.at[rows].set(vals, mode="drop")

    return cluster._replace(
        allocatable=scat(cluster.allocatable, nr, delta.allocatable),
        requested=scat(cluster.requested, nr, delta.requested),
        nonzero_requested=scat(cluster.nonzero_requested, nr,
                               delta.nonzero_requested),
        node_valid=scat(cluster.node_valid, nr, delta.node_valid),
        unschedulable=scat(cluster.unschedulable, nr, delta.unschedulable),
        kv=scat(cluster.kv, nr, _densify_ids(delta.kv_ids, L=L)),
        keymask=scat(cluster.keymask, nr, delta.keymask),
        num=scat(cluster.num, nr, delta.num),
        topo_pair=scat(cluster.topo_pair, nr, delta.topo_pair),
        taints=scat(cluster.taints, nr, delta.taints),
        ports=scat(cluster.ports, nr, delta.ports),
        images=scat(cluster.images, nr, delta.images),
        avoid_hot=scat(cluster.avoid_hot, nr, delta.avoid_hot),
        zone_hot=scat(cluster.zone_hot, nr, delta.zone_hot),
        image_size=jnp.asarray(delta.image_size),
        image_spread=jnp.asarray(delta.image_spread),
        taint_is_hard=jnp.asarray(delta.taint_is_hard),
        taint_is_prefer=jnp.asarray(delta.taint_is_prefer),
        pod_kv=scat(cluster.pod_kv, pr, _densify_ids(delta.pod_kv_ids, L=L)),
        pod_key=scat(cluster.pod_key, pr, delta.pod_key),
        pod_ns_hot=scat(cluster.pod_ns_hot, pr, delta.pod_ns_hot),
        pod_node=scat(cluster.pod_node, pr, delta.pod_node),
        pod_valid=scat(cluster.pod_valid, pr, delta.pod_valid),
        pod_terminating=scat(cluster.pod_terminating, pr,
                             delta.pod_terminating))


# the donated variant updates the resident buffers in place (the cluster
# lives on device across cycles and nobody else may hold it); the
# no-donate twin serves the pipelined drain's rare case where a
# dispatched-but-uncommitted cycle still reads the same buffers
_apply_cluster_delta_donated = jax.jit(_apply_cluster_delta,
                                       donate_argnums=(0,))
_apply_cluster_delta_shared = jax.jit(_apply_cluster_delta)


def apply_cluster_delta(cluster, delta, donate: bool = True):
    """Apply a ClusterDelta on device.  delta leaves must already be
    pow2-bucketed (state/tensors.gather_delta) so repeated same-bucket
    deltas hit one compiled program.  donate=False keeps the input
    buffers alive (in-flight pipelined reader)."""
    delta = jax.tree.map(jnp.asarray, delta)
    fn = (_apply_cluster_delta_donated if donate
          else _apply_cluster_delta_shared)
    return fn(cluster, delta)


def _apply_terms_delta(filter_slots, score_slots, filter_delta, score_delta):
    """Scatter one cycle's written rows (state/tensors.py TermsDelta, one a
    table) into the per-row leaves of the two resident existing-term tables
    (``term_slots``: ``sel.index``, ``ns_hot``, ``topo_key``, ``pod_idx``,
    ``weight``, ``valid``).  A program of its own beside
    _apply_cluster_delta: that one compiles per (node rows, pod rows) pair
    already, and an owner comes or goes in few of the cycles it serves.
    Pads are one-past-capacity rows, dropped; a table nothing was written
    to takes an all-pad delta."""
    def scat(slots, delta):
        return tuple(x.at[delta.rows].set(v, mode="drop")
                     for x, v in zip(slots, delta[1:]))
    return scat(filter_slots, filter_delta), scat(score_slots, score_delta)


# donated and shared as _apply_cluster_delta's two are, and for its reasons
_apply_terms_delta_donated = jax.jit(_apply_terms_delta,
                                     donate_argnums=(0, 1))
_apply_terms_delta_shared = jax.jit(_apply_terms_delta)


def apply_terms_delta(filter_terms, score_terms, filter_delta, score_delta,
                      donate: bool = True):
    """Apply a TermsDelta to each resident term table on device; returns
    the two ExistingTerms.  The deltas' rows are bucketed by the caller
    (state/tensors.gather_terms_delta), so a steady drain hits one compiled
    program.  donate=False keeps the input buffers alive."""
    from ..state.tensors import term_slots, with_term_slots
    fn = (_apply_terms_delta_donated if donate
          else _apply_terms_delta_shared)
    # the deltas go in as the numpy leaves they are: the call transfers
    # them together, where a jnp.asarray a leaf would one at a time
    fs, ss = fn(term_slots(filter_terms), term_slots(score_terms),
                filter_delta, score_delta)
    return with_term_slots(filter_terms, fs), with_term_slots(score_terms, ss)


@functools.partial(jax.jit, static_argnames=("cfg",))
def filter_and_score(cluster, batch, cfg: ProgramConfig,
                     host_ok=None) -> FilterScoreResult:
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    feasible, unresolvable, affinity_ok = run_filters(cluster, batch, cfg,
                                                      host_ok)
    scores, per_plugin = run_scores(cluster, batch, cfg, feasible, affinity_ok)
    return FilterScoreResult(feasible=feasible, unresolvable=unresolvable,
                             scores=scores, plugin_scores=per_plugin)


@jax.jit
def nominated_fit_mask(cluster, batch, nom):
    """The nominated-pods overlay pass (reference: addNominatedPods +
    two-pass filtering, core/generic_scheduler.go:530,594-612): for each
    pod, nominated pods of EQUAL-OR-GREATER priority — excluding the pod
    ITSELF when it is the nominator — are treated as already running on
    their nominated nodes, and the pod must fit with that usage added.  The
    second (overlay-free) pass of the reference is the main filter program,
    so ANDing this mask in reproduces the two-pass rule for the resource
    dimension (topology-term contributions of nominated pods are not
    overlaid — a documented deviation, see models/batch.py NominatedPods).

    The mask differs from all-True only at the <=M nominated node rows, so
    the work is [B, M, R] (M = nominated pods, tiny) — never [B, N, R].
    Returns [B, N] bool."""
    from .batch import densify_for
    from ..ops import kernels as K
    batch = densify_for(cluster, batch)
    B = batch.priority.shape[0]
    N = cluster.allocatable.shape[0]
    M = nom.node.shape[0]
    ok_entry = nom.valid & (nom.node >= 0)
    # w[b, j]: entry j reserves capacity against pod b
    w = (nom.prio[None, :] >= batch.priority[:, None]) & ok_entry[None, :] \
        & (nom.self_row[None, :] != jnp.arange(B)[:, None])
    # same_node[m, j]: entry j lands on slot m's node (duplicates collapse:
    # every slot on a node carries that node's FULL applicable sum)
    same_node = (nom.node[None, :] == nom.node[:, None]) & ok_entry[None, :]
    overlay = jnp.einsum("bj,mj,jr->bmr", w.astype(jnp.float32),
                         same_node.astype(jnp.float32), nom.req,
                         preferred_element_type=jnp.float32)  # [B, M, R]
    rows = jnp.clip(nom.node, 0, N - 1)
    free = cluster.allocatable[rows] - cluster.requested[rows]  # [M, R]
    ok = K.fit_rows(jnp.broadcast_to(batch.req[:, None, :], overlay.shape),
                    free[None, :, :] - overlay)                 # [B, M]
    mask = jnp.ones((B, N), bool).at[:, rows].min(
        jnp.where(ok_entry[None, :], ok, True))
    return mask


@functools.partial(jax.jit, static_argnames=("cfg",))
def nominated_topology_mask(cluster, nom_batch, nom_rows, nom_prio, batch,
                            cfg: ProgramConfig):
    """Topology dimension of addNominatedPods (generic_scheduler.go:530):
    nominated pods become EXISTING pods placed on their nominated nodes —
    labels, namespaces and required anti-affinity terms included — and the
    batch re-runs its InterPodAffinity + PodTopologySpread filters against
    that extended cluster.  ANDed with the overlay-free main pass this
    reproduces the reference's two-pass rule for the topology dimension:
    a nominated pod can REPEL or SKEW lower/equal-priority pods but never
    satisfy their affinity (the without-pass still gates).

    Per-row applicability (only nominated pods of >= priority are visible,
    :536) is gated at row granularity: rows where NO nominated pod
    qualifies pass untouched.  Rows where only a SUBSET qualifies see the
    full overlay — a conservative (over-blocking) deviation, exact in the
    common case where nominated pods outrank the whole batch.

    Returns [B, N] bool."""
    from .batch import densify_for
    from .gang import _extend_cluster  # lazy: gang imports this module

    batch = densify_for(cluster, batch)
    nom_batch = densify_for(cluster, nom_batch)
    ext = _extend_cluster(cluster, nom_batch)
    M = nom_batch.valid.shape[0]
    placed = nom_batch.valid & (nom_rows >= 0)
    ext = ext._replace(
        pod_node=jnp.concatenate([cluster.pod_node,
                                  jnp.asarray(nom_rows, jnp.int32)]),
        pod_valid=jnp.concatenate([cluster.pod_valid, placed]))
    affinity_ok = K.node_affinity_filter(ext, batch)
    ok = jnp.ones((batch.valid.shape[0], cluster.allocatable.shape[0]), bool)
    if "PodTopologySpread" in cfg.filters:
        ok = ok & K.spread_filter(ext, batch, affinity_ok,
                                  active_keys=cfg.active_keys)
    if "InterPodAffinity" in cfg.filters:
        ipa_ok, _ = K.interpod_filter(ext, batch,
                                      active_keys=cfg.active_keys)
        ok = ok & ipa_ok
    affected = jnp.any(placed[None, :]
                       & (nom_prio[None, :] >= batch.priority[:, None]),
                       axis=1)
    return jnp.where(affected[:, None], ok, True)


def select_host(scores: jnp.ndarray, feasible: jnp.ndarray,
                rng: jnp.ndarray) -> jnp.ndarray:
    """Masked argmax with uniform tie-break among max-score nodes
    (reference: generic_scheduler.go:217 selectHost — reservoir sampling;
    here a seeded categorical over the tie set, equivalent in distribution).
    Returns [B] node index, -1 when no feasible node."""
    B = scores.shape[0]
    neg = jnp.float32(-2**62)
    masked = jnp.where(feasible, scores, neg)
    best = jnp.max(masked, axis=1, keepdims=True)
    ties = (masked == best) & feasible
    logits = jnp.where(ties, 0.0, neg)
    keys = jax.random.split(rng, B)
    choice = jax.vmap(lambda k, lg: jax.random.categorical(k, lg))(keys, logits)
    has = jnp.any(feasible, axis=1)
    return jnp.where(has, choice.astype(jnp.int32), -1)


@functools.partial(jax.jit, static_argnames=("cfg",))
def schedule_batch(cluster, batch, cfg: ProgramConfig, rng, host_ok=None):
    """One-shot independent scheduling of a batch: every pod scored against
    the same snapshot (no intra-batch interactions).  Used for gang/auction
    modes and as the building block of the sequential scan program."""
    res = filter_and_score(cluster, batch, cfg, host_ok)
    chosen = select_host(res.scores, res.feasible, rng)
    return res, chosen
