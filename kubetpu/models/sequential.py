"""Sequential-replay scheduling as one on-device lax.scan.

The reference schedules pods strictly serially because pod i's binding
changes pod i+1's filter/score inputs (reference: pkg/scheduler/scheduler.go
:509 scheduleOne; cache.AssumePod :435).  The TPU-native redesign keeps those
exact semantics but runs the whole batch in ONE compiled program: all
O(B x P x N) matching work is precomputed as batched matmuls, and a lax.scan
over the pod axis carries the small mutable state a placement creates:

  - node resource vectors (requested / non-zero requested / pod count)
  - topology-pair match counts for PodTopologySpread (hard + soft)
  - pair counts for InterPodAffinity (incoming required terms, existing
    anti-affinity, scoring contributions)
  - per-node matching-pod counts (hostname spread, DefaultPodTopologySpread)
  - hostPort conflicts between batch pods

so each scan step does only O(N + T*L) elementwise work plus two [L]x[N,L]
matvecs — no per-pod host round-trip, no re-snapshotting.  Step i sees
exactly the cluster state the reference's serial loop would see after
placements 0..i-1 (assumed pods included).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..ops import kernels as K
from ..ops.selectors import match_selectors
from .programs import ProgramConfig, UNRESOLVABLE_FILTERS

_f = K._f


class SeqResult(NamedTuple):
    chosen: jnp.ndarray        # [B] i32 node row, -1 unschedulable
    score: jnp.ndarray         # [B] f32 winning score
    n_feasible: jnp.ndarray    # [B] i32 feasible-node count at the pod's turn
                               # (of the SAMPLED search when sampling binds)
    all_unresolvable: jnp.ndarray  # [B] bool — every failed node failed
                               # UnschedulableAndUnresolvable (preemption
                               # cannot help; scheduler.go:391 preempt gate)
    requested: jnp.ndarray     # [N, R] final requested (for host cache sync checks)
    next_start: jnp.ndarray    # i32 — rotated start index after the batch
                               # (reference: nextStartNodeIndex,
                               # generic_scheduler.go:451,487)
    packed: jnp.ndarray        # [3*B+1] i32 = concat(chosen, n_feasible,
                               # all_unresolvable, [next_start]) — the
                               # host's whole per-cycle view in ONE
                               # device->host readback (one host sync
                               # per cycle)


def _num_feasible_nodes_to_find(n_valid, pct: int):
    """reference: generic_scheduler.go:54-59,379-399 numFeasibleNodesToFind.
    n_valid is traced (i32); pct is static."""
    if pct >= 100:
        return n_valid
    adaptive = pct if pct > 0 else jnp.maximum(50 - n_valid // 125, 5)
    num = jnp.maximum(n_valid * adaptive // 100, 100)
    return jnp.where(n_valid < 100, n_valid, num)


def _term_state(cluster, terms, B):
    """Base pair counts and node-pair maps for a PodTerms set."""
    T = terms.valid.shape[1]
    N = cluster.allocatable.shape[0]
    L = cluster.kv.shape[1]
    m = K._pod_term_matches(cluster, terms, B)  # [B, T, P]
    ep_pair = K.pod_topo_pairs(cluster, terms.topo_key.reshape(-1))
    node_pair = K.node_topo_pairs(cluster, terms.topo_key.reshape(-1))
    has_key = (node_pair >= 0).reshape(B, T, N) & terms.topo_known[:, :, None]
    return m, ep_pair, node_pair, has_key


def _batch_term_matches(terms, batch, B):
    """Match pod-side terms against the *batch's own* pods -> [B*T, B]."""
    m = match_selectors(terms.sel, batch.kv_hot, batch.key_hot)  # [B*T, B]
    T = terms.valid.shape[1]
    ns_ok = jnp.einsum("btn,in->bti", terms.ns_hot, batch.ns_hot,
                       preferred_element_type=jnp.float32) > 0.5
    m = m.reshape(B, T, B) & ns_ok & terms.valid[:, :, None] & batch.valid[None, None, :]
    return m.reshape(B * T, B)


def schedule_sequential(cluster, batch, cfg: ProgramConfig, rng,
                        hard_pod_affinity_weight: float = 1.0,
                        host_ok=None, start_index=0,
                        score_bias=None) -> SeqResult:
    """Python entry for the jitted scan — same required dispatch-bug
    workaround as gang.schedule_gang (one Python frame between callers and
    the jit object; see that docstring).  score_bias: optional [B, N] f32
    of weighted host-plugin scores (framework runner's Score/NormalizeScore
    extension point) added to the device total before selectHost.

    AOT seam (utils/aot.py): armed, a signature hit runs the deserialized
    build-time executable; disarmed this is the plain jit call."""
    from ..utils import aot
    return aot.dispatch(
        "_schedule_sequential", _schedule_sequential,
        (cluster, batch, cfg, rng),
        dict(hard_pod_affinity_weight=hard_pod_affinity_weight,
             host_ok=host_ok, start_index=start_index,
             score_bias=score_bias),
        static_argnums=(2,))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=())
def _schedule_sequential(cluster, batch, cfg: ProgramConfig, rng,
                         hard_pod_affinity_weight: float = 1.0,
                         host_ok=None, start_index=0,
                         score_bias=None) -> SeqResult:
    return _sequential_program(
        cluster, batch, cfg, rng,
        hard_pod_affinity_weight=hard_pod_affinity_weight,
        host_ok=host_ok, start_index=start_index, score_bias=score_bias)


def _sequential_program(cluster, batch, cfg: ProgramConfig, rng,
                        hard_pod_affinity_weight: float = 1.0,
                        host_ok=None, start_index=0,
                        score_bias=None) -> SeqResult:
    """The scan program body, jit-free: `_schedule_sequential` above is
    its single-device jit root, and the shard_map mesh path
    (parallel/shardmap.py) traces the SAME body per device — the pod-axis
    mesh correctness fix replicates this serial scan explicitly instead
    of letting the legacy SPMD partitioner mis-lower its cross-shard
    index selection."""
    from .batch import densify_for
    batch = densify_for(cluster, batch)
    B = batch.req.shape[0]
    N = cluster.allocatable.shape[0]
    L = cluster.kv.shape[1]
    filters = set(cfg.filters)
    score_w = dict(cfg.scores)
    # adaptive sampling: each pod searches only the first `limit` feasible
    # nodes in rotated processing order, then advances the start index by
    # the number of nodes examined (generic_scheduler.go:379-399,451,487)
    sample = cfg.percentage_of_nodes_to_score < 100
    # dtype pinned: integer jnp.sum promotes to the DEFAULT int, which is
    # i64 wherever x64 is enabled — and n_valid feeds the i32 'start'
    # scan carry (census/f64-promotion)
    n_valid = jnp.sum(cluster.node_valid, dtype=jnp.int32)
    sample_limit = _num_feasible_nodes_to_find(
        n_valid, cfg.percentage_of_nodes_to_score)

    # ---------------- static precompute (batched, MXU-heavy) ----------------
    base = cluster.node_valid[None, :] & batch.valid[:, None]
    if host_ok is not None:
        base = base & host_ok
    affinity_ok = K.node_affinity_filter(cluster, batch)
    static_ok = base
    static_unres = jnp.zeros_like(base)

    def apply_static(name, ok):
        nonlocal static_ok, static_unres
        if name in filters:
            if name in UNRESOLVABLE_FILTERS:
                static_unres = static_unres | (~ok & base)
            static_ok = static_ok & ok

    apply_static("NodeUnschedulable", K.node_unschedulable_filter(cluster, batch))
    apply_static("NodeName", K.node_name_filter(cluster, batch))
    apply_static("NodeAffinity", affinity_ok)
    apply_static("TaintToleration", K.taint_filter(cluster, batch))
    if "NodeLabel" in filters:
        nl_present, nl_absent, _ = cfg.arg("NodeLabel", ((), (), ()))
        apply_static("NodeLabel",
                     K.node_label_filter(cluster, batch, nl_present, nl_absent))

    ports_ok0 = K.node_ports_filter(cluster, batch) if "NodePorts" in filters else None

    ns_eq = jnp.einsum("bn,in->bi", batch.ns_hot, batch.ns_hot,
                       preferred_element_type=jnp.float32) > 0.5  # [B, B]
    not_term = batch.valid  # new pods are never terminating

    # --- spread hard
    use_sph = "PodTopologySpread" in filters
    if use_sph:
        cons = batch.spread
        C = cons.topo_key.shape[1]
        st = K._spread_state(cluster, batch, cons, affinity_ok,
                             cluster.node_valid[None, :] & jnp.ones((B, N), bool))
        sph_m_bb = match_selectors(cons.sel, batch.kv_hot, batch.key_hot)  # [BC, B]
        sph_m_bb = (_f(sph_m_bb.reshape(B, C, B)
                       & ns_eq[:, None, :] & not_term[None, None, :])
                    .reshape(B * C, B))
        sph = dict(st=st, cons=cons, C=C, m_bb=sph_m_bb,
                   has_cons=jnp.any(cons.valid, axis=1))

    # --- spread soft (score)
    use_sps = "PodTopologySpread" in score_w
    if use_sps:
        scons = batch.spread_soft
        Cs = scons.topo_key.shape[1]
        count_mask = affinity_ok & cluster.node_valid[None, :]
        sst = K._spread_state(cluster, batch, scons, jnp.zeros_like(affinity_ok),
                              count_mask)
        # registration is per-step (depends on the pod's feasible set); the
        # precomputed registered mask is unused — counts and node_counts are.
        all_keys_s = jnp.all(sst.has_key | ~scons.valid[:, :, None], axis=1)
        cm_soft = count_mask & all_keys_s  # nodes whose pods are counted
        sps_m_bb = match_selectors(scons.sel, batch.kv_hot, batch.key_hot)
        sps_m_bb = (_f(sps_m_bb.reshape(B, Cs, B)
                       & ns_eq[:, None, :] & not_term[None, None, :])
                    .reshape(B * Cs, B))
        is_host = (scons.topo_key == cfg.hostname_topokey) & scons.topo_known
        sps = dict(st=sst, cons=scons, Cs=Cs, m_bb=sps_m_bb, is_host=is_host,
                   cm_soft=cm_soft, all_keys=all_keys_s)

    # --- interpod filter
    use_ipf = "InterPodAffinity" in filters
    if use_ipf:
        ra, raa = batch.ra, batch.raa
        Tr, Ta = ra.valid.shape[1], raa.valid.shape[1]
        m_ra, ep_ra, np_ra, hk_ra = _term_state(cluster, ra, B)
        match_all = jnp.all(m_ra | ~ra.valid[:, :, None], axis=1)  # [B, P]
        ra_pair0 = K.pair_scatter(
            jnp.broadcast_to(match_all[:, None, :], m_ra.shape).reshape(B * Tr, -1),
            ep_ra, L)
        m_raa, ep_raa, np_raa, hk_raa = _term_state(cluster, raa, B)
        raa_pair0 = K.pair_scatter(m_raa.reshape(B * Ta, -1), ep_raa, L)

        ra_ind_bb = _batch_term_matches(ra, batch, B)  # [BTr, B]
        ra_all_bb = jnp.all((ra_ind_bb.reshape(B, Tr, B) > 0)
                            | ~ra.valid[:, :, None], axis=1)  # [B, B]
        has_ra = jnp.any(ra.valid, axis=1)
        ra_all_bb = _f(ra_all_bb & has_ra[:, None] & batch.valid[None, :])
        raa_ind_bb = _batch_term_matches(raa, batch, B)  # [BTa, B]

        # existing pods' required anti-affinity -> [B, L] base counts
        ft = cluster.filter_terms
        em = match_selectors(ft.sel, batch.kv_hot, batch.key_hot)
        ens = jnp.einsum("en,bn->eb", ft.ns_hot, batch.ns_hot,
                         preferred_element_type=jnp.float32) > 0.5
        em = em & ens & ft.valid[:, None]
        pod_topo = jnp.take(cluster.topo_pair, jnp.clip(cluster.pod_node, 0, None),
                            axis=0)
        e_pair = jnp.take_along_axis(pod_topo[jnp.clip(ft.pod_idx, 0, None)],
                                     ft.topo_key[:, None], axis=1)[:, 0]
        owner_ok = jnp.take(cluster.pod_valid, jnp.clip(ft.pod_idx, 0, None))
        e_pair = jnp.where(ft.valid & owner_ok, e_pair, -1)
        ids = jnp.where(e_pair >= 0, e_pair, L)
        ea_cnt0 = jax.ops.segment_sum(_f(em), ids, num_segments=L + 1)[:L].T  # [B, L]

        self_all = jnp.all(ra.self_match | ~ra.valid, axis=1) & has_ra
        ipf = dict(Tr=Tr, Ta=Ta, ra=ra, raa=raa, np_ra=np_ra, hk_ra=hk_ra,
                   np_raa=np_raa, hk_raa=hk_raa, ra_pair0=ra_pair0,
                   raa_pair0=raa_pair0, ra_all_bb=ra_all_bb, ra_ind_bb=ra_ind_bb,
                   raa_ind_bb=raa_ind_bb, ea_cnt0=ea_cnt0, self_all=self_all,
                   has_ra=has_ra)

    # --- interpod score
    use_ips = "InterPodAffinity" in score_w
    if use_ips:
        pt = batch.pref
        Tp = pt.valid.shape[1]
        m_p, ep_p, np_p, hk_p = _term_state(cluster, pt, B)
        data = _f(m_p) * pt.weight[:, :, None] * _f(pt.valid)[:, :, None]
        pref_pair0 = K.pair_scatter(data.reshape(B * Tp, -1), ep_p, L)  # [BTp, L]

        st_terms = cluster.score_terms
        em = match_selectors(st_terms.sel, batch.kv_hot, batch.key_hot)
        ens = jnp.einsum("en,bn->eb", st_terms.ns_hot, batch.ns_hot,
                         preferred_element_type=jnp.float32) > 0.5
        owner_ok = jnp.take(cluster.pod_valid, jnp.clip(st_terms.pod_idx, 0, None))
        em = (_f(em & ens & st_terms.valid[:, None] & owner_ok[:, None])
              * st_terms.weight[:, None])
        pod_topo = jnp.take(cluster.topo_pair, jnp.clip(cluster.pod_node, 0, None),
                            axis=0)
        e_pair = jnp.take_along_axis(pod_topo[jnp.clip(st_terms.pod_idx, 0, None)],
                                     st_terms.topo_key[:, None], axis=1)[:, 0]
        e_pair = jnp.where(st_terms.valid & owner_ok, e_pair, -1)
        ids = jnp.where(e_pair >= 0, e_pair, L)
        sc_cnt0 = jax.ops.segment_sum(em, ids, num_segments=L + 1)[:L].T  # [B, L]

        pref_w_bb = _f(_batch_term_matches(pt, batch, B)) \
            * (pt.weight * _f(pt.valid)).reshape(B * Tp, 1)  # [BTp, B]
        # hard (required) affinity terms of a placed pod scored at hardWeight
        ra_s = batch.ra
        Trs = ra_s.valid.shape[1]
        hard_bb = _f(_batch_term_matches(ra_s, batch, B)) \
            * hard_pod_affinity_weight  # [BTr, B]
        _, _, np_ra_s, _ = _term_state(cluster, ra_s, B)
        ips = dict(Tp=Tp, pt=pt, np_p=np_p, pref_pair0=pref_pair0,
                   sc_cnt0=sc_cnt0, pref_w_bb=pref_w_bb, hard_bb=hard_bb,
                   np_ra_s=np_ra_s, Trs=Trs, ra_s=ra_s)

    # --- default spread (score)
    use_ds = "DefaultPodTopologySpread" in score_w
    if use_ds:
        ds_raw0 = K.default_spread_score(cluster, batch)  # [B, N]
        ds_m = match_selectors(batch.spread_selector, batch.kv_hot, batch.key_hot)
        ds_bb = _f(ds_m & ns_eq & not_term[None, :]
                   & ~batch.spread_skip[:, None])  # [B, B]

    # --- static score rows
    image_score = (K.image_locality_score(cluster, batch)
                   if "ImageLocality" in score_w else None)
    avoid_score = (K.prefer_avoid_pods_score(cluster, batch)
                   if "NodePreferAvoidPods" in score_w else None)
    node_aff_raw = (K.node_affinity_score(cluster, batch)
                    if "NodeAffinity" in score_w else None)
    taint_raw = (K.taint_toleration_score(cluster, batch)
                 if "TaintToleration" in score_w else None)
    limits_score = (K.resource_limits_score(cluster, batch)
                    if "NodeResourceLimits" in score_w else None)
    nodelabel_score = (K.node_label_score(cluster, batch,
                                          cfg.arg("NodeLabel", ((), (), ()))[2])
                       if "NodeLabel" in score_w else None)
    rtcr_args = (cfg.arg("RequestedToCapacityRatio",
                         (((0, 0), (100, 100)), ((0, 0, 1), (1, 0, 1))))
                 if "RequestedToCapacityRatio" in score_w else None)

    # ---------------- scan ----------------
    neg = jnp.float32(-2**62)
    big = jnp.float32(2**62)

    def row_normalize(raw_row, feas_row, reverse):
        max_c = jnp.maximum(jnp.max(jnp.where(feas_row, raw_row, neg)), 0.0)
        scaled = K._idiv(K.MAX_NODE_SCORE * raw_row, jnp.maximum(max_c, 1.0))
        if reverse:
            scaled = K.MAX_NODE_SCORE - scaled
        zero_case = K.MAX_NODE_SCORE if reverse else 0.0
        out = jnp.where(max_c > 0, scaled, zero_case)
        return jnp.where(feas_row, out, 0.0)

    carry0 = {
        "req": cluster.requested,
        "nz": cluster.nonzero_requested,
    }
    if sample:
        carry0["start"] = jnp.asarray(start_index, jnp.int32)
    if ports_ok0 is not None:
        # ports the scan's own placements have registered per node; existing
        # pods' ports are already inside ports_ok0 via cluster.ports
        carry0["ports_used"] = jnp.zeros((N, batch.ports_hot.shape[1]),
                                         jnp.float32)
    if use_sph:
        carry0["sph_cnt"] = sph["st"].pair_counts
    if use_sps:
        carry0["sps_cnt"] = sps["st"].pair_counts
        carry0["sps_node"] = sps["st"].node_counts.reshape(B * sps["Cs"], N)
    if use_ipf:
        carry0["ra_cnt"] = ipf["ra_pair0"]
        carry0["raa_cnt"] = ipf["raa_pair0"]
        carry0["ea_cnt"] = ipf["ea_cnt0"]
    if use_ips:
        carry0["pref_cnt"] = ips["pref_pair0"]
        carry0["sc_own"] = ips["sc_cnt0"]
    if use_ds:
        carry0["ds_cnt"] = ds_raw0

    kv_f = _f(cluster.kv)

    def step(carry, i):
        feas = static_ok[i]
        unres = static_unres[i]

        # ---- dynamic filters
        if "NodeResourcesFit" in filters:
            alloc = cluster.allocatable
            req_i = batch.req[i]
            free_ok = alloc >= req_i[None, :] + carry["req"]
            R = alloc.shape[1]
            ch = jnp.arange(R)
            is_fixed = (ch < K.N_FIXED_CHANNELS) & (ch != K.CH_PODS)
            check = jnp.where(is_fixed, True, req_i[None, :] > 0)
            res_ok = jnp.all(free_ok | ~check | (ch == K.CH_PODS)[None, :], axis=-1)
            pods_ok = free_ok[:, K.CH_PODS]
            zero_req = jnp.all(jnp.where(ch == K.CH_PODS, 0.0, req_i) == 0)
            feas = feas & pods_ok & (zero_req | res_ok)

        if ports_ok0 is not None:
            conflict = carry["ports_used"] @ batch.ports_hot[i] > 0.5  # [N]
            feas = feas & ports_ok0[i] & ~conflict

        if use_sph:
            C = sph["C"]
            st = sph["st"]
            cnt = jax.lax.dynamic_slice_in_dim(carry["sph_cnt"], i * C, C)  # [C, L]
            reg = jax.lax.dynamic_slice_in_dim(st.registered, i * C, C)
            npair = jax.lax.dynamic_slice_in_dim(st.node_pair, i * C, C)  # [C, N]
            min_match = jnp.min(jnp.where(reg, cnt, big), axis=1)  # [C]
            mn = K.pair_gather(jnp.where(reg, cnt, 0.0), npair)  # [C, N]
            skew = mn + _f(sph["cons"].self_match[i])[:, None] - min_match[:, None]
            c_ok = st.has_key[i] & (skew <= sph["cons"].max_skew[i][:, None])
            ok = jnp.all(c_ok | ~sph["cons"].valid[i][:, None], axis=0)
            ok = jnp.where(sph["has_cons"][i] & st.any_eligible[i], ok, True)
            feas = feas & ok

        if use_ipf:
            Tr, Ta = ipf["Tr"], ipf["Ta"]
            ra, raa = ipf["ra"], ipf["raa"]
            cnt_r = jax.lax.dynamic_slice_in_dim(carry["ra_cnt"], i * Tr, Tr)
            np_r = jax.lax.dynamic_slice_in_dim(ipf["np_ra"], i * Tr, Tr)
            c_at = K.pair_gather(cnt_r, np_r)  # [Tr, N]
            term_ok = ipf["hk_ra"][i] & (c_at > 0.5)
            aff_ok = jnp.all(term_ok | ~ra.valid[i][:, None], axis=0)
            no_matches = jnp.sum(cnt_r) < 0.5
            all_keys = jnp.all(ipf["hk_ra"][i] | ~ra.valid[i][:, None], axis=0)
            aff_ok = aff_ok | (no_matches & ipf["self_all"][i] & all_keys)
            aff_ok = jnp.where(ipf["has_ra"][i], aff_ok, True)

            cnt_a = jax.lax.dynamic_slice_in_dim(carry["raa_cnt"], i * Ta, Ta)
            np_a = jax.lax.dynamic_slice_in_dim(ipf["np_raa"], i * Ta, Ta)
            ca = K.pair_gather(cnt_a, np_a)
            anti_fail = jnp.any(ipf["hk_raa"][i] & (ca > 0.5)
                                & raa.valid[i][:, None], axis=0)
            exist_fail = (carry["ea_cnt"][i] @ kv_f.T) > 0.5
            unres = unres | (~aff_ok & static_ok[i])
            feas = feas & aff_ok & ~anti_fail & ~exist_fail

        # ---- adaptive sampling: keep only the first `sample_limit`
        # feasible nodes in rotated processing order (reference:
        # findNodesThatFit's stop-at-numFeasibleNodesToFind + the
        # nextStartNodeIndex rotation, generic_scheduler.go:451-487)
        if sample:
            start = carry["start"]
            k = jnp.arange(N)
            in_range = k < n_valid
            nv = jnp.maximum(n_valid, 1)
            perm = jnp.where(in_range, (start + k) % nv, 0)
            feas_perm = jnp.where(in_range, feas[perm], False)
            cum = jnp.cumsum(feas_perm.astype(jnp.int32))
            allowed_perm = feas_perm & (cum <= sample_limit)
            total_feas = cum[-1]
            reached = cum >= sample_limit
            # argmax returns the DEFAULT int dtype, which widens to i64
            # wherever x64 is enabled and breaks the i32 'start' carry
            # (census/f64-promotion); pin the index dtype
            kth_pos = jnp.argmax(reached).astype(jnp.int32)
            n_processed = jnp.where(total_feas >= sample_limit,
                                    kth_pos + 1, n_valid)
            feas = jnp.zeros((N,), bool).at[perm].max(allowed_perm)
            new_start = (start + n_processed) % nv

        # ---- scores
        total = jnp.zeros((N,), jnp.float32)
        nz_req = carry["nz"]
        alloc_cpu = cluster.allocatable[:, K.CH_CPU]
        alloc_mem = cluster.allocatable[:, K.CH_MEM]
        req_cpu = nz_req[:, 0] + batch.nonzero_req[i, 0]
        req_mem = nz_req[:, 1] + batch.nonzero_req[i, 1]

        if "NodeResourcesBalancedAllocation" in score_w:
            s = K.balanced_formula(req_cpu, req_mem, alloc_cpu, alloc_mem)
            total += jnp.where(feas, s, 0.0) * score_w["NodeResourcesBalancedAllocation"]

        if "NodeResourcesLeastAllocated" in score_w:
            s = K._idiv(K.least_formula(req_cpu, alloc_cpu)
                        + K.least_formula(req_mem, alloc_mem), 2.0)
            total += jnp.where(feas, s, 0.0) * score_w["NodeResourcesLeastAllocated"]

        if "NodeResourcesMostAllocated" in score_w:
            s = K._idiv(K.most_formula(req_cpu, alloc_cpu)
                        + K.most_formula(req_mem, alloc_mem), 2.0)
            total += jnp.where(feas, s, 0.0) * score_w["NodeResourcesMostAllocated"]

        if image_score is not None:
            total += jnp.where(feas, image_score[i], 0.0) * score_w["ImageLocality"]
        if avoid_score is not None:
            total += jnp.where(feas, avoid_score[i], 0.0) * score_w["NodePreferAvoidPods"]
        if limits_score is not None:
            total += jnp.where(feas, limits_score[i], 0.0) * score_w["NodeResourceLimits"]
        if nodelabel_score is not None:
            total += jnp.where(feas, nodelabel_score[i], 0.0) * score_w["NodeLabel"]
        if rtcr_args is not None:
            shape, resources = rtcr_args
            parts = []
            for kind, ch, weight in resources:
                if kind == 0:
                    req, cap = req_cpu, alloc_cpu
                elif kind == 1:
                    req, cap = req_mem, alloc_mem
                elif ch < 0:
                    req = jnp.zeros_like(req_cpu)
                    cap = jnp.zeros_like(alloc_cpu)
                else:
                    cap = cluster.allocatable[:, ch]
                    req = carry["req"][:, ch] + batch.req[i, ch]
                parts.append((req, cap, weight))
            rtcr = K.rtcr_combine(parts, shape)
            total += jnp.where(feas, rtcr, 0.0) * score_w["RequestedToCapacityRatio"]
        if node_aff_raw is not None:
            total += row_normalize(node_aff_raw[i], feas, False) * score_w["NodeAffinity"]
        if taint_raw is not None:
            total += row_normalize(taint_raw[i], feas, True) * score_w["TaintToleration"]

        if use_ips:
            Tp = ips["Tp"]
            pc = jax.lax.dynamic_slice_in_dim(carry["pref_cnt"], i * Tp, Tp)
            counts = jnp.sum(pc, axis=0) + carry["sc_own"][i]  # [L]
            raw = counts @ kv_f.T  # [N]
            any_counts = jnp.any(counts != 0)
            max_c = jnp.maximum(jnp.max(jnp.where(feas, raw, neg)), 0.0)
            min_c = jnp.minimum(jnp.min(jnp.where(feas, raw, big)), 0.0)
            diff = max_c - min_c
            norm = jnp.where(diff > 0,
                             K._idiv(K.MAX_NODE_SCORE * (raw - min_c),
                                     jnp.maximum(diff, 1.0)), 0.0)
            s = jnp.where(any_counts, norm, raw)
            total += jnp.where(feas, s, 0.0) * score_w["InterPodAffinity"]

        if use_sps:
            Cs = sps["Cs"]
            sst = sps["st"]
            scons = sps["cons"]
            cnt = jax.lax.dynamic_slice_in_dim(carry["sps_cnt"], i * Cs, Cs)  # [Cs, L]
            ncnt = jax.lax.dynamic_slice_in_dim(carry["sps_node"], i * Cs, Cs)  # [Cs, N]
            npair = jax.lax.dynamic_slice_in_dim(sst.node_pair, i * Cs, Cs)
            valid = scons.valid[i]
            is_host = sps["is_host"][i]
            all_keys = sps["all_keys"][i]
            ignored = feas & ~all_keys
            scored = feas & all_keys
            # per-step registration from this pod's feasible set
            elig = scored[None, :] & (npair >= 0)
            reg = K.pair_scatter(elig, npair, L) > 0.5  # [Cs, L]
            reg = reg & ~is_host[:, None]
            topo_size = jnp.sum(_f(reg), axis=1)
            n_scored = jnp.sum(_f(scored))
            size = jnp.where(is_host, n_scored, topo_size)
            pair_c = K.pair_gather(jnp.where(reg, cnt, 0.0), npair)  # [Cs, N]
            cval = jnp.where(is_host[:, None], ncnt, pair_c)
            ms = scons.max_skew[i][:, None]
            cval = jnp.where(cval < ms, ms - 1.0, cval)
            raw = K.log_weighted_floor(
                cval, size,
                (valid & scons.topo_known[i])[:, None] & sst.has_key[i],
                N + 1)
            raw = jnp.where(ignored, 0.0, raw)
            min_s = jnp.min(jnp.where(scored, raw, big))
            max_s = jnp.maximum(jnp.max(jnp.where(scored, raw, neg)), 0.0)
            norm = jnp.where(max_s > 0,
                             K._idiv(K.MAX_NODE_SCORE * (max_s + jnp.minimum(min_s, big)
                                                         - raw),
                                     jnp.maximum(max_s, 1.0)),
                             K.MAX_NODE_SCORE)
            s = jnp.where(ignored, 0.0, norm)
            s = jnp.where(jnp.any(valid), s, K.MAX_NODE_SCORE)
            total += jnp.where(feas, s, 0.0) * score_w["PodTopologySpread"]

        if use_ds:
            raw = carry["ds_cnt"][i]
            max_node = jnp.maximum(jnp.max(jnp.where(feas, raw, neg)), 0.0)
            zh = cluster.zone_hot          # [N, Z], zero rows when zoneless
            has_zone = jnp.any(zh > 0, axis=1)
            zcounts = jnp.einsum("n,nz->z", jnp.where(feas, raw, 0.0), zh,
                                 precision=jax.lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)
            have_zones = jnp.any(feas & has_zone)
            max_zone = jnp.maximum(jnp.max(zcounts), 0.0)
            f_score = jnp.where(max_node > 0,
                                K.MAX_NODE_SCORE * (max_node - raw)  # kubelint: ignore[numeric/score-div] reference computes fScore in float64 (default_pod_topology_spread.go:126); floor lands after the zone combine
                                / jnp.maximum(max_node, 1.0), K.MAX_NODE_SCORE)
            nzc = jnp.einsum("z,nz->n", zcounts, zh,
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
            z_score = jnp.where(max_zone > 0,
                                K.MAX_NODE_SCORE * (max_zone - nzc)  # kubelint: ignore[numeric/score-div] reference computes zoneScore in float64 (default_pod_topology_spread.go:142); floor lands after the combine
                                / jnp.maximum(max_zone, 1.0), K.MAX_NODE_SCORE)
            wz = (f_score * (1.0 - K.ZONE_WEIGHTING)) + K.ZONE_WEIGHTING * z_score
            s = jnp.floor(jnp.where(have_zones & has_zone, wz, f_score))
            s = jnp.where(batch.spread_skip[i], 0.0, s)
            total += jnp.where(feas, s, 0.0) * score_w["DefaultPodTopologySpread"]

        # ---- select
        if score_bias is not None:
            total = total + score_bias[i]
        masked = jnp.where(feas, total, neg)
        best = jnp.max(masked)
        ties = (masked == best) & feas
        logits = jnp.where(ties, 0.0, neg)
        choice = jax.random.categorical(jax.random.fold_in(rng, i), logits)
        has = jnp.any(feas)
        chosen = jnp.where(has, choice.astype(jnp.int32), -1)
        n_feas = jnp.sum(feas.astype(jnp.int32))
        # host-filter failures stay RESOLVABLE for the preemption gate
        # (host_ok is folded into base but not into this exclusion mask)
        base_nodes_i = cluster.node_valid & batch.valid[i]
        all_unres = jnp.all(unres | feas | ~base_nodes_i)
        win_score = jnp.where(has, best, 0.0)

        # ---- apply placement to carries (no-op when unschedulable)
        ok = has & batch.valid[i]
        node = jnp.clip(chosen, 0, N - 1)
        w = jnp.where(ok, 1.0, 0.0)

        new = dict(carry)
        new["req"] = carry["req"].at[node].add(batch.req[i] * w)
        new["nz"] = carry["nz"].at[node].add(batch.nonzero_req[i] * w)
        if sample:
            # padded (invalid) pods must not advance the rotation
            new["start"] = jnp.where(batch.valid[i], new_start, carry["start"])
        if ports_ok0 is not None:
            new["ports_used"] = carry["ports_used"].at[node].max(
                batch.ports_asnode_hot[i] * w)
        if use_sph:
            ids = sph["st"].node_pair[:, node]  # [BC]
            vals = sph["m_bb"][:, i] * w * _f(ids >= 0)
            new["sph_cnt"] = carry["sph_cnt"].at[
                jnp.arange(ids.shape[0]), jnp.clip(ids, 0, None)].add(vals)
        if use_sps:
            ids = sps["st"].node_pair[:, node]
            in_mask = jnp.repeat(sps["cm_soft"][:, node], sps["Cs"])
            vals = sps["m_bb"][:, i] * w * _f(ids >= 0) * _f(in_mask)
            new["sps_cnt"] = carry["sps_cnt"].at[
                jnp.arange(ids.shape[0]), jnp.clip(ids, 0, None)].add(vals)
            new["sps_node"] = carry["sps_node"].at[:, node].add(
                sps["m_bb"][:, i] * w * _f(in_mask))
        if use_ipf:
            Tr, Ta = ipf["Tr"], ipf["Ta"]
            ids = ipf["np_ra"][:, node]
            vals = jnp.repeat(ipf["ra_all_bb"][:, i], Tr) * w * _f(ids >= 0)
            new["ra_cnt"] = carry["ra_cnt"].at[
                jnp.arange(ids.shape[0]), jnp.clip(ids, 0, None)].add(vals)
            ids = ipf["np_raa"][:, node]
            vals = ipf["raa_ind_bb"][:, i] * w * _f(ids >= 0)
            new["raa_cnt"] = carry["raa_cnt"].at[
                jnp.arange(ids.shape[0]), jnp.clip(ids, 0, None)].add(vals)
            # pod i's own anti terms now repel matching future pods
            own_ids = jax.lax.dynamic_slice_in_dim(ipf["np_raa"], i * Ta, Ta)[:, node]
            own_m = jax.lax.dynamic_slice_in_dim(ipf["raa_ind_bb"], i * Ta, Ta)  # [Ta, B]
            vals = own_m.T * w * _f(own_ids >= 0)[None, :]  # [B, Ta]
            new["ea_cnt"] = carry["ea_cnt"].at[
                :, jnp.clip(own_ids, 0, None)].add(vals)
        if use_ips:
            Tp, Trs = ips["Tp"], ips["Trs"]
            ids = ips["np_p"][:, node]
            vals = ips["pref_w_bb"][:, i] * w * _f(ids >= 0)
            new["pref_cnt"] = carry["pref_cnt"].at[
                jnp.arange(ids.shape[0]), jnp.clip(ids, 0, None)].add(vals)
            own_ids = jax.lax.dynamic_slice_in_dim(ips["np_p"], i * Tp, Tp)[:, node]
            own_m = jax.lax.dynamic_slice_in_dim(ips["pref_w_bb"], i * Tp, Tp)
            vals = own_m.T * w * _f(own_ids >= 0)[None, :]
            new["sc_own"] = carry["sc_own"].at[:, jnp.clip(own_ids, 0, None)].add(vals)
            own_ids = jax.lax.dynamic_slice_in_dim(ips["np_ra_s"], i * Trs, Trs)[:, node]
            own_m = jax.lax.dynamic_slice_in_dim(ips["hard_bb"], i * Trs, Trs)
            vals = own_m.T * w * _f(own_ids >= 0)[None, :]
            new["sc_own"] = new["sc_own"].at[:, jnp.clip(own_ids, 0, None)].add(vals)
        if use_ds:
            new["ds_cnt"] = carry["ds_cnt"].at[:, node].add(ds_bb[:, i] * w)

        out = (chosen, win_score, n_feas, all_unres)
        return new, out

    carry, (chosen, score, n_feas, all_unres) = jax.lax.scan(
        step, carry0, jnp.arange(B))
    next_start = carry["start"] if sample else jnp.asarray(start_index,
                                                           jnp.int32)
    packed = jnp.concatenate([chosen, n_feas, all_unres.astype(jnp.int32),
                              next_start[None]])
    return SeqResult(chosen=chosen, score=score, n_feasible=n_feas,
                     all_unresolvable=all_unres, requested=carry["req"],
                     next_start=next_start, packed=packed)
