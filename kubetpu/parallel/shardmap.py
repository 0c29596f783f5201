"""Explicit shard_map programs for pod-axis mesh scale-out.

The GSPMD path (parallel/mesh.py, ``partitioner="gspmd"``) lets the SPMD
partitioner derive every intermediate sharding.  On this jax the LEGACY
partitioner mis-lowers the auction/scan loop machinery when the POD axis
is split — gang contention winners flip and infeasible pods come back
placed (PR 6's env-gated skip markers document the fault class; the
[B, N] kernel family itself lowers correctly, which is why
``schedule_batch`` passes at (2, 4) ungated).  This module sidesteps the
partitioner for the selection core entirely: the cross-shard program is
written out as an explicit ``shard_map`` with hand-placed collectives, so
there is no partitioning decision left for the legacy lowering to get
wrong.

Two surfaces, chosen statically per dispatch (``gang_surface``):

* ``tiled`` — the scale path, term-free batches (``gang_surface``
  states the surface; ``build_bundle`` below provides the
  round-invariant [S, B, N] planes).  Each device owns a [B/mp, N/mn]
  tile of the filter/score plane; per auction round it

    1. recomputes feasibility + the weighted score combine on its tile
       (per-pod normalization statistics via ``lax.pmax/pmin/psum`` over
       the "nodes" axis — every reduction is a float max/min or an
       integer-valued-f32 sum, exact in any order below 2**24),
    2. proposes GATHER-FREE: the selectHost categorical decomposes into
       ``argmax(where(tie, gumbel, -2**62))`` EXACTLY in f32 (the
       auction's logits are 0 / -2**62, so gumbel + logits is that
       where), and the cross-shard argmax resolves without any
       cross-shard gather — a strict-improvement (best, gumbel) pmax
       pair plus a pmin over
       qualifying GLOBAL node indices reproduces jnp.argmax's
       first-index tie-break bit-for-bit,
    3. resolves contention collectively: per-pod winners
       ``lax.all_gather`` over the "pods" axis and every device runs the
       IDENTICAL O(B) segmented-reduce admission
       (models/gang.py admission_mask/admission_sums — the same
       functions the single-device round calls), so no readback, sort or
       carry ever leaves the device.

  The [B, N] plane work — the term that forces the north-star shape off
  one chip — is the part that shards over BOTH mesh axes; the [N, R]
  capacity carries and [B] assignment vector ride replicated (~100 KB at
  10k nodes).

* ``replicated`` — the correctness surface for everything else
  (intra-batch topology, exotic score plugins, non-divisible axes):
  every device traces the SAME single-device program body
  (``gang._gang_program`` / ``sequential._sequential_program``) on
  replicated inputs.  Bit-identity with the single-device golden is by
  construction — it IS the single-device program, and shard_map's manual
  lowering leaves the partitioner nothing to mis-lower.  This replicates
  compute across the mesh (documented; the scale story is the tiled
  auction — topology batches joining it is ROADMAP item 2's intra-batch
  surface work).

The delta scatter gets the same treatment: ``apply_cluster_delta_mesh``
shifts the replicated [D]-row tables into each shard's LOCAL row space
(out-of-shard rows map one-past-capacity, which ``mode="drop"``
discards) and applies the ordinary ``programs._apply_cluster_delta``
per shard — the resident cluster stays pre-sharded across cycles on the
pod axis too, with no cross-shard scatter for the partitioner to lower.

Meshes enter jit static args as a registry KEY (axis layout + device
ids) rather than the Mesh object: the key digests stably into the AOT
signature (utils/aot.py) while the trace-time body looks the Mesh back
up from ``_MESHES``.
"""

from __future__ import annotations

import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import gang, programs, sequential
from ..models.gang import GangResult, admission_mask, admission_sums
from ..ops import kernels as K
from ..state.tensors import CH_CPU, CH_MEM, CH_PODS, N_FIXED_CHANNELS

AXIS_PODS = "pods"
AXIS_NODES = "nodes"
_NEG = jnp.float32(-2**62)
MAX_NODE_SCORE = K.MAX_NODE_SCORE

# trace-time Mesh registry: the hashable KEY is the jit/AOT static, the
# Mesh object never enters a signature.  Written by register_mesh (any
# thread that dispatches), read at trace time.
_mesh_lock = threading.Lock()
_MESHES: Dict[tuple, Mesh] = {}   # kubelint: guarded-by(_mesh_lock)


def mesh_key(mesh: Mesh) -> tuple:
    """Stable hashable identity of a mesh: axis layout + device ids +
    platform (two same-shape meshes over different chips must key — and
    so AOT-sign — distinctly)."""
    devs = tuple(int(d.id) for d in mesh.devices.flat)
    plat = mesh.devices.flat[0].platform
    return (tuple(mesh.shape.items()), devs, plat)


def register_mesh(mesh: Mesh) -> tuple:
    key = mesh_key(mesh)
    with _mesh_lock:
        _MESHES[key] = mesh  # kubelint: ignore[purity/global-mutate] trace-time mesh registry: written under _mesh_lock by the dispatch wrappers, read only at TRACE time to resolve the hashable static key back to its Mesh — never inside traced computation
    return key


def _get_mesh(key: tuple) -> Mesh:
    with _mesh_lock:
        return _MESHES[key]


def _rep_spec(tree):
    """Per-leaf replicated spec pytree (shard_map also takes prefixes,
    but an explicit per-leaf tree survives None-leaves and NamedTuples
    of pytrees uniformly)."""
    return jax.tree.map(lambda _: P(), tree)


# score plugins whose raw matrix is round-invariant under
# intra_batch_topology=False and enters the tiled auction as a plane
_PLANE_OF = {
    "ImageLocality": "raw:ImageLocality",
    "NodeAffinity": "raw:NodeAffinity",
    "NodePreferAvoidPods": "raw:NodePreferAvoidPods",
    "TaintToleration": "raw:TaintToleration",
    "InterPodAffinity": "ipa_raw",
    "DefaultPodTopologySpread": "dps_raw",
}

# the score family the tiled auction combines: the planes above plus the
# scorers it recomputes per round from the evolving requested/nonzero
# carries; anything else dispatches on "replicated"
SUPPORTED_SCORES = frozenset(_PLANE_OF) | frozenset({
    "NodeResourcesBalancedAllocation",
    "NodeResourcesLeastAllocated",
    "NodeResourcesMostAllocated",
    # the no-soft-constraints constant path (MaxNodeScore on every
    # feasible node): exactly what a term-free batch evaluates to
    "PodTopologySpread",
})


def plane_order(cfg, has_bias: bool) -> Tuple[str, ...]:
    """Static plane layout of the stacked [S, B, N] input: score raws in
    cfg.scores order, then the optional host score bias, then the
    selectHost gumbel matrix (always last)."""
    names = []
    for name, _ in cfg.scores:
        key = _PLANE_OF.get(name)
        if key is not None and key not in names:
            names.append(key)
    if has_bias:
        names.append("bias")
    names.append("gumbel")
    return tuple(names)


def build_bundle(cluster, batch, cfg, static_ok, ports_ok0, score_pre,
                 score_bias, gumbel) -> Dict[str, jnp.ndarray]:
    """Precompute the tiled auction's round-invariant inputs, once per
    auction (traced inside _shardmap_gang).  All [B, N] planes here are
    assignment-independent under intra_batch_topology=False: the pod axis
    is frozen during the loop, so interpod/default-spread raws are
    round-invariant even though their single-device twins recompute per
    round."""
    B = batch.req.shape[0]
    planes: Dict[str, jnp.ndarray] = {}
    ipa_any = jnp.zeros((B,), bool)
    for name, _ in cfg.scores:
        if name == "InterPodAffinity" and "ipa_raw" not in planes:
            raw, any_counts = K.interpod_score_raw(
                cluster, batch, pre=score_pre.get("interpod_score"),
                active_keys=cfg.active_keys)
            planes["ipa_raw"] = raw
            ipa_any = any_counts[:, 0]
        elif name == "DefaultPodTopologySpread" and "dps_raw" not in planes:
            planes["dps_raw"] = K.default_spread_score(
                cluster, batch, match_ns=score_pre.get("default_spread"))
        elif name in _PLANE_OF and _PLANE_OF[name] not in planes:
            planes[_PLANE_OF[name]] = score_pre["raw:" + name]
    if score_bias is not None:
        planes["bias"] = score_bias
    planes["gumbel"] = gumbel
    order = plane_order(cfg, score_bias is not None)
    stack = jnp.stack([planes[k].astype(jnp.float32) for k in order])
    zone = cluster.zone_hot
    if zone.shape[1] == 0:
        zone = jnp.zeros((zone.shape[0], 1), jnp.float32)
    return dict(
        planes=stack,                         # [S, B, N] f32
        mask=static_ok & ports_ok0,           # [B, N] bool
        ipa_any=ipa_any,                      # [B] bool
        skip=batch.spread_skip,               # [B] bool
        breq=batch.req,                       # [B, R] f32
        bnz=batch.nonzero_req,                # [B, 2] f32
        bports=batch.ports_hot,               # [B, P] f32
        alloc=cluster.allocatable,            # [N, R] f32 (node side)
        zone=zone,                            # [N, Z] f32 (node side)
    )


def gang_surface(cfg, intra_batch_topology: bool, batch, mesh,
                 n_nodes: int, n_pods: int) -> str:
    """The static surface this (cfg, routing, batch, mesh) dispatches
    on.  "tiled" needs: intra_batch_topology off (the pod axis is frozen
    during the loop, so every score raw is a round-invariant plane),
    every score plugin in SUPPORTED_SCORES, no soft spread constraints
    in the batch (host-side numpy inspection — a device-array batch
    skips the check and its caller carries the term-free contract, which
    the scheduler's needs_topo gate does: soft-spread batches route
    intra_batch_topology=True and land on "replicated" here).  Both
    sharded axes must divide exactly — shard_map, unlike GSPMD, does
    not pad."""
    if intra_batch_topology:
        return "replicated"
    for name, _ in cfg.scores:
        if name not in SUPPORTED_SCORES:
            return "replicated"
    sv = getattr(getattr(batch, "spread_soft", None), "valid", None)
    if isinstance(sv, np.ndarray) and bool(sv.any()):
        return "replicated"
    mp = mesh.shape[AXIS_PODS]
    mn = mesh.shape[AXIS_NODES]
    if n_pods % mp or n_nodes % mn:
        return "replicated"
    return "tiled"


# --------------------------------------------------------------------------
# gang


@functools.partial(jax.jit,
                   static_argnames=("cfg", "mesh_key", "max_rounds",
                                    "intra_batch_topology",
                                    "residual_window", "surface"))
def _shardmap_gang(cluster, batch, cfg, rng, mesh_key,
                   host_ok=None, score_bias=None,
                   max_rounds: Optional[int] = None,
                   intra_batch_topology: bool = True,
                   residual_window: int = 512,
                   surface: str = "replicated") -> GangResult:
    """The mesh gang jit root (one per (cfg, mesh, surface) static
    combination).  AOT seam name "_shardmap_gang"."""
    mesh = _get_mesh(mesh_key)
    if surface == "tiled":
        return _gang_tiled(cluster, batch, cfg, rng, mesh,
                           host_ok=host_ok, score_bias=score_bias,
                           max_rounds=max_rounds,
                           residual_window=residual_window)
    return _gang_replicated(cluster, batch, cfg, rng, mesh,
                            host_ok=host_ok, score_bias=score_bias,
                            max_rounds=max_rounds,
                            intra_batch_topology=intra_batch_topology,
                            residual_window=residual_window)


def _gang_replicated(cluster, batch, cfg, rng, mesh, host_ok, score_bias,
                     max_rounds, intra_batch_topology, residual_window):
    """Every device traces the single-device auction body on replicated
    inputs — bit-identity by construction (it IS _gang_program)."""
    dyn = {}
    if host_ok is not None:
        dyn["host_ok"] = host_ok
    if score_bias is not None:
        dyn["score_bias"] = score_bias

    def body(cl, b, r, dk):
        return gang._gang_program(
            cl, b, cfg, r, max_rounds=max_rounds,
            intra_batch_topology=intra_batch_topology,
            residual_window=residual_window, **dk)

    out_struct = jax.eval_shape(body, cluster, batch, rng, dyn)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_rep_spec(cluster), _rep_spec(batch), P(),
                  _rep_spec(dyn)),
        out_specs=_rep_spec(out_struct),
        check_vma=False)(cluster, batch, rng, dyn)


def _gang_tiled(cluster, batch, cfg, rng, mesh, host_ok, score_bias,
                max_rounds, residual_window):
    """The gather-free tiled auction: round-invariant planes,
    node-axis collective stats, pods-axis all_gather resolution,
    replicated admission.  Bit-match oracle: models/gang.py's
    single-device auction at intra_batch_topology=False."""
    from ..models.batch import densify_for
    from ..models.programs import run_filters, static_raw_scores

    batch = densify_for(cluster, batch)
    B = batch.req.shape[0]
    N = cluster.allocatable.shape[0]
    R = cluster.allocatable.shape[1]
    Pn = batch.ports_hot.shape[1]
    if max_rounds is None:
        max_rounds = B
    filters = set(cfg.filters)
    use_fit = "NodeResourcesFit" in filters
    use_ports = "NodePorts" in filters
    use_window = bool(residual_window) and residual_window < B  # kubelint: ignore[host-sync/cast] trace-time constant: residual_window is a static int (jit static_argnames on _shardmap_gang)

    # ---- round-invariant precompute at GSPMD level: the static-filter
    # and raw-score kernel family lowers correctly on every supported
    # mesh shape (schedule_batch's ungated (2,4) equivalence is the
    # evidence); only the LOOP below needs the explicit program.
    static_ok, static_unres, _affinity_ok = run_filters(
        cluster, batch, cfg, host_ok,
        skip=("NodeResourcesFit", "NodePorts"))
    ports_ok0 = (K.node_ports_filter(cluster, batch) if use_ports
                 else jnp.ones((B, N), bool))
    score_pre = dict(static_raw_scores(cluster, batch, cfg))
    pod_idx = jnp.arange(B, dtype=jnp.int32)
    tie_keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(pod_idx)
    gumbel = jax.vmap(
        lambda k: jax.random.gumbel(k, (N,), jnp.float32))(tie_keys)
    bundle = build_bundle(cluster, batch, cfg, static_ok, ports_ok0,
                          score_pre, score_bias, gumbel)

    mp = mesh.shape[AXIS_PODS]
    mn = mesh.shape[AXIS_NODES]
    Bl, Nl = B // mp, N // mn
    Z = bundle["zone"].shape[1]
    plane = {name: i
             for i, name in enumerate(plane_order(
                 cfg, score_bias is not None))}
    scores_static = tuple((n, float(w)) for n, w in cfg.scores)  # kubelint: ignore[host-sync/cast] trace-time constant: weights are static ints from cfg.scores (jit static arg)

    def body(planes, mask_t, unres_t, breq, bnz, bports,
             basnode, ipa_any, skipb, validb, alloc, zone, nodev,
             req0, nz0):
        po = lax.axis_index(AXIS_PODS) * Bl
        no = lax.axis_index(AXIS_NODES) * Nl
        gum_t = planes[plane["gumbel"]]
        alloc_t = lax.dynamic_slice_in_dim(alloc, no, Nl)
        zone_t = lax.dynamic_slice_in_dim(zone, no, Nl)
        nv_t = lax.dynamic_slice_in_dim(nodev, no, Nl)
        breq_l = lax.dynamic_slice_in_dim(breq, po, Bl)
        bnz_l = lax.dynamic_slice_in_dim(bnz, po, Bl)
        bports_l = lax.dynamic_slice_in_dim(bports, po, Bl)
        skip_l = lax.dynamic_slice_in_dim(skipb, po, Bl)
        ipaany_l = lax.dynamic_slice_in_dim(ipa_any, po, Bl)
        valid_l = lax.dynamic_slice_in_dim(validb, po, Bl)
        has_zone = jnp.any(zone_t > 0, axis=1)   # [Nl]

        def feas_tile(c, live):
            """The round's feasibility on the shard's tile: the static
            mask AND'd with the fit verdict against committed usage and
            the hostPort conflict against registered ports — the same
            f32/bool op sequence as gang.py's feasibility()."""
            f = mask_t & live[:, None]
            if use_fit:
                used_t = lax.dynamic_slice_in_dim(c["req"], no, Nl)
                pods_ok = (alloc_t[:, CH_PODS][None, :]
                           >= breq_l[:, CH_PODS][:, None]
                           + used_t[:, CH_PODS][None, :])
                res_ok = jnp.ones((Bl, Nl), bool)
                zero_req = jnp.ones((Bl,), bool)
                for r_ in range(R):
                    if r_ == CH_PODS:
                        continue
                    free_ok = (alloc_t[:, r_][None, :]
                               >= breq_l[:, r_][:, None]
                               + used_t[:, r_][None, :])
                    if r_ < N_FIXED_CHANNELS:
                        res_ok = res_ok & free_ok
                    else:
                        res_ok = res_ok & (free_ok
                                           | (breq_l[:, r_] <= 0)[:, None])
                    zero_req = zero_req & (breq_l[:, r_] == 0)
                f = f & pods_ok & (zero_req[:, None] | res_ok)
            if use_ports:
                pu_t = lax.dynamic_slice_in_dim(c["ports_used"], no, Nl)
                conflict = jnp.dot(bports_l, pu_t.T,
                                   preferred_element_type=jnp.float32) > 0.5
                f = f & ~conflict
            return f

        def resource_fracs(c):
            nz_t = lax.dynamic_slice_in_dim(c["nz"], no, Nl)
            req_cpu = nz_t[:, 0][None, :] + bnz_l[:, 0][:, None]
            req_mem = nz_t[:, 1][None, :] + bnz_l[:, 1][:, None]
            alloc_cpu = jnp.broadcast_to(alloc_t[:, CH_CPU][None, :],
                                         (Bl, Nl))
            alloc_mem = jnp.broadcast_to(alloc_t[:, CH_MEM][None, :],
                                         (Bl, Nl))
            return req_cpu, req_mem, alloc_cpu, alloc_mem

        def stats_for(f):
            """Per-pod normalization statistics, tile reduce +
            "nodes"-axis collective.  Float max/min are exactly
            associative; the DPS zone sums are integer-valued f32, exact
            under psum below 2**24."""
            st = {}
            st["act"] = K.exact_pmax(
                jnp.max(f.astype(jnp.float32), axis=1), AXIS_NODES)
            names = {n for n, _ in scores_static}
            if "NodeAffinity" in names:
                raw = planes[plane["raw:NodeAffinity"]]
                st["max_na"] = K.exact_pmax(
                    jnp.max(jnp.where(f, raw, _NEG), axis=1), AXIS_NODES)
            if "TaintToleration" in names:
                raw = planes[plane["raw:TaintToleration"]]
                st["max_tt"] = K.exact_pmax(
                    jnp.max(jnp.where(f, raw, _NEG), axis=1), AXIS_NODES)
            if "InterPodAffinity" in names:
                raw = planes[plane["ipa_raw"]]
                st["max_ip"] = K.exact_pmax(
                    jnp.max(jnp.where(f, raw, _NEG), axis=1), AXIS_NODES)
                st["min_ip"] = K.exact_pmin(
                    jnp.min(jnp.where(f, raw, -_NEG), axis=1), AXIS_NODES)
            if "DefaultPodTopologySpread" in names:
                raw = planes[plane["dps_raw"]]
                st["max_dps"] = K.exact_pmax(
                    jnp.max(jnp.where(f, raw, _NEG), axis=1), AXIS_NODES)
                st["havez"] = K.exact_pmax(
                    jnp.max((f & has_zone[None, :]).astype(jnp.float32),
                            axis=1), AXIS_NODES)
                # integer-valued f32 counts: exact under psum below 2**24
                # (tools/kubeexact proves the bound at north-star shapes)
                st["czone"] = K.exact_psum(
                    jnp.dot(jnp.where(f, raw, 0.0), zone_t,
                            preferred_element_type=jnp.float32),
                    AXIS_NODES)
            return st

        def combine(c, f, st):
            """The weighted score combine on the tile: same formula
            helpers, same accumulation order as run_scores."""
            total = jnp.zeros((Bl, Nl), jnp.float32)
            for name, weight in scores_static:
                if name == "NodeResourcesBalancedAllocation":
                    s = K.balanced_formula(*resource_fracs(c))
                elif name == "NodeResourcesLeastAllocated":
                    rc, rm, ac, am = resource_fracs(c)
                    s = K._idiv(K.least_formula(rc, ac) * 1.0
                                + K.least_formula(rm, am) * 1.0, 2.0)
                elif name == "NodeResourcesMostAllocated":
                    rc, rm, ac, am = resource_fracs(c)
                    s = K._idiv(K.most_formula(rc, ac) * 1.0
                                + K.most_formula(rm, am) * 1.0, 2.0)
                elif name == "ImageLocality":
                    s = planes[plane["raw:ImageLocality"]]
                elif name == "NodePreferAvoidPods":
                    s = planes[plane["raw:NodePreferAvoidPods"]]
                elif name == "NodeAffinity":
                    raw = planes[plane["raw:NodeAffinity"]]
                    max_c = jnp.maximum(st["max_na"], 0.0)
                    scaled = K._idiv(MAX_NODE_SCORE * raw,
                                     jnp.maximum(max_c, 1.0)[:, None])
                    s = jnp.where((max_c > 0)[:, None], scaled, 0.0)
                elif name == "TaintToleration":
                    raw = planes[plane["raw:TaintToleration"]]
                    max_c = jnp.maximum(st["max_tt"], 0.0)
                    scaled = MAX_NODE_SCORE - K._idiv(
                        MAX_NODE_SCORE * raw,
                        jnp.maximum(max_c, 1.0)[:, None])
                    s = jnp.where((max_c > 0)[:, None], scaled,
                                  MAX_NODE_SCORE)
                elif name == "InterPodAffinity":
                    raw = planes[plane["ipa_raw"]]
                    max_c = jnp.maximum(st["max_ip"], 0.0)
                    min_c = jnp.minimum(st["min_ip"], 0.0)
                    diff = max_c - min_c
                    norm = jnp.where(
                        (diff > 0)[:, None],
                        K._idiv(MAX_NODE_SCORE * (raw - min_c[:, None]),
                                jnp.maximum(diff, 1.0)[:, None]), 0.0)
                    s = jnp.where(ipaany_l[:, None], norm, raw)
                elif name == "PodTopologySpread":
                    # no-soft-constraints constant path: exactly what a
                    # term-free batch evaluates to (the surface gate
                    # routes soft-spread batches to "replicated")
                    s = jnp.where(f, MAX_NODE_SCORE, 0.0)
                elif name == "DefaultPodTopologySpread":
                    raw = planes[plane["dps_raw"]]
                    max_node = jnp.maximum(st["max_dps"], 0.0)
                    f_score = jnp.where(
                        (max_node > 0)[:, None],
                        MAX_NODE_SCORE * (max_node[:, None] - raw)  # kubelint: ignore[numeric/score-div] reference computes fScore in float64 (default_pod_topology_spread.go:126); mirrors the single-device twin exactly
                        / jnp.maximum(max_node, 1.0)[:, None],
                        MAX_NODE_SCORE)
                    cz = st["czone"]
                    max_zone = jnp.maximum(jnp.max(cz, axis=1), 0.0)
                    nzc = jnp.dot(cz, zone_t.T,
                                  preferred_element_type=jnp.float32)
                    zone_score = jnp.where(
                        (max_zone > 0)[:, None],
                        MAX_NODE_SCORE * (max_zone[:, None] - nzc)  # kubelint: ignore[numeric/score-div] reference computes zoneScore in float64 (default_pod_topology_spread.go:142); mirrors the single-device twin exactly
                        / jnp.maximum(max_zone, 1.0)[:, None],
                        MAX_NODE_SCORE)
                    with_zone = (f_score * (1.0 - K.ZONE_WEIGHTING)
                                 + K.ZONE_WEIGHTING * zone_score)
                    havez = st["havez"] > 0
                    out = jnp.where(havez[:, None] & has_zone[None, :],
                                    with_zone, f_score)
                    out = jnp.floor(out)
                    s = jnp.where(skip_l[:, None], 0.0, out)
                else:  # pragma: no cover - gang_surface gates this
                    raise ValueError(
                        "shard_map tiled surface: unsupported score "
                        "kernel %s" % name)
                total = total + jnp.where(f, s, 0.0) * weight
            if "bias" in plane:
                total = total + planes[plane["bias"]]
            return total

        def round_t(c, in_window, windowed: bool):
            assigned_l = lax.dynamic_slice_in_dim(c["assigned"], po, Bl)
            live = (assigned_l < 0) & valid_l
            if in_window is not None:
                live = live & lax.dynamic_slice_in_dim(in_window, po, Bl)
            f = feas_tile(c, live)
            st = stats_for(f)
            total = combine(c, f, st)
            # gather-free cross-shard argmax, first-index tie-break:
            # per-tile gumbel decomposition then MIN global index among
            # exact (score, gumbel) ties — the earliest index IS
            # jnp.argmax's choice (blessed ops/kernels.py pair)
            tile_best, tile_h, tile_arg = K.gumbel_tiebreak_argmax(
                total, f, gum_t, no, _NEG)
            best, gidx = K.crossaxis_first_index_argmax(
                tile_best, tile_h, tile_arg, AXIS_NODES, _NEG)
            active_l = st["act"] > 0
            prop_l = jnp.where(active_l, gidx, N).astype(jnp.int32)
            # collective host resolution: winners to every device, then
            # the IDENTICAL replicated O(B) admission everywhere
            prop = lax.all_gather(prop_l, AXIS_PODS, tiled=True)
            active = lax.all_gather(active_l, AXIS_PODS, tiled=True)
            bestg = lax.all_gather(best, AXIS_PODS, tiled=True)
            live_g = lax.all_gather(live, AXIS_PODS, tiled=True)

            admit = admission_mask(prop, active, breq, bports, basnode,
                                   alloc, c["req"], use_ports, N)
            add_req, add_nz, add_ports = admission_sums(
                admit, prop, breq, bnz, basnode, use_ports, N)
            new = dict(c)
            new["req"] = c["req"] + add_req
            new["nz"] = c["nz"] + add_nz
            if use_ports:
                new["ports_used"] = jnp.maximum(c["ports_used"], add_ports)
            new["assigned"] = jnp.where(admit, prop, c["assigned"])
            new["win_score"] = jnp.where(admit, bestg, c["win_score"])
            new["feas0"] = jnp.where(c["rounds"] == 0, f, c["feas0"])
            admitted_any = jnp.any(admit)
            new["rounds"] = c["rounds"] + 1
            new["admits"] = c["admits"] + admitted_any.astype(jnp.int32)
            new["cap_deferred"] = c["cap_deferred"] + jnp.sum(
                active & ~admit, dtype=jnp.int32)
            if windowed:
                new_retire = (~active) & live_g & ~c["retired"]
                new["retired"] = jnp.where(
                    admitted_any, jnp.zeros_like(c["retired"]),
                    c["retired"] | new_retire)
                new["progress"] = admitted_any | jnp.any(new_retire)
            else:
                new["progress"] = admitted_any
            return new

        carry0 = dict(
            req=req0, nz=nz0,
            ports_used=jnp.zeros((N, Pn), jnp.float32),
            assigned=jnp.full((B,), -1, jnp.int32),
            win_score=jnp.zeros((B,), jnp.float32),
            feas0=jnp.zeros((Bl, Nl), bool),
            rounds=jnp.int32(0), admits=jnp.int32(0),
            cap_deferred=jnp.int32(0),
            progress=jnp.bool_(True),
            retired=jnp.zeros((B,), bool))

        if max_rounds < 1:
            out = carry0
        elif not use_window:
            def cond(c):
                return c["progress"] & (c["rounds"] < max_rounds)

            out = lax.while_loop(cond, lambda c: round_t(c, None, False),
                                 carry0)
        else:
            # phase A: one full-width round (windowed retirement
            # bookkeeping); phase B: rounds over the first
            # residual_window still-unassigned pods — selected by MASK,
            # not row-gather (a gather would reshuffle the pod shards
            # every round); non-window pods propose the no-op segment,
            # which leaves every other segment's prefix sums untouched,
            # so admission equals the gathered lax form exactly
            out = round_t(carry0, None, True)

            def condw(c):
                pool = (c["assigned"] < 0) & validb & ~c["retired"]
                return (c["progress"] & jnp.any(pool)
                        & (c["admits"] < max_rounds))

            def bodyw(c):
                pool = (c["assigned"] < 0) & validb & ~c["retired"]
                in_w = pool & (jnp.cumsum(pool.astype(jnp.int32))
                               <= residual_window)
                return round_t(c, in_w, True)

            out = lax.while_loop(condw, bodyw, out)

        f0 = out["feas0"]
        n_feas = lax.all_gather(
            K.exact_psum(jnp.sum(f0.astype(jnp.int32), axis=1),
                         AXIS_NODES),
            AXIS_PODS, tiled=True)
        base_t = nv_t[None, :] & valid_l[:, None]
        au_l = jnp.all(unres_t | f0 | ~base_t, axis=1)
        au_l = K.exact_pmin(au_l.astype(jnp.int32), AXIS_NODES) > 0
        all_unres = lax.all_gather(au_l, AXIS_PODS, tiled=True)
        return (out["assigned"], out["win_score"], out["rounds"],
                out["req"], out["nz"], out["ports_used"], f0, n_feas,
                all_unres, out["cap_deferred"])

    tile2 = P(AXIS_PODS, AXIS_NODES)
    (assigned, win_score, rounds, req, nz, ports_used, feas0, n_feas,
     all_unres, cap_deferred) = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, AXIS_PODS, AXIS_NODES), tile2, tile2,
                  P(), P(), P(), P(), P(), P(), P(), P(), P(), P(),
                  P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P(), tile2, P(), P(), P()),
        check_vma=False)(
        bundle["planes"], bundle["mask"], static_unres,
        bundle["breq"], bundle["bnz"], bundle["bports"],
        batch.ports_asnode_hot, bundle["ipa_any"], bundle["skip"],
        batch.valid, bundle["alloc"], bundle["zone"], cluster.node_valid,
        cluster.requested, cluster.nonzero_requested)

    packed = jnp.concatenate([assigned, n_feas,
                              all_unres.astype(jnp.int32),
                              rounds.reshape(1)])
    return GangResult(chosen=assigned, score=win_score, rounds=rounds,
                      requested=req, nz=nz, ports_used=ports_used,
                      feasible0=feas0, unresolvable=static_unres,
                      n_feasible=n_feas, all_unresolvable=all_unres,
                      packed=packed, capacity_deferred=cap_deferred,
                      # a batch with a soft constraint never tiles
                      # (gang_surface): nothing to read
                      soft_spread_skew=jnp.int32(-1))


# --------------------------------------------------------------------------
# sequential


@functools.partial(jax.jit, static_argnames=("cfg", "mesh_key"))
def _shardmap_sequential(cluster, batch, cfg, rng, mesh_key,
                         hard_pod_affinity_weight=1.0, host_ok=None,
                         start_index=0, score_bias=None):
    """The mesh sequential jit root: the serial scan is replicated per
    device (its per-step work is O(N + T*L); the pod axis is serial BY
    CONSTRUCTION, so there is no cross-pod parallelism to shard —
    explicit replication is the correctness fix for the legacy
    partitioner's cross-shard index selection).  AOT seam name
    "_shardmap_sequential"."""
    mesh = _get_mesh(mesh_key)
    dyn = dict(hard_pod_affinity_weight=hard_pod_affinity_weight,
               start_index=start_index)
    if host_ok is not None:
        dyn["host_ok"] = host_ok
    if score_bias is not None:
        dyn["score_bias"] = score_bias

    def body(cl, b, r, dk):
        return sequential._sequential_program(cl, b, cfg, r, **dk)

    out_struct = jax.eval_shape(body, cluster, batch, rng, dyn)
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(_rep_spec(cluster), _rep_spec(batch), P(),
                  _rep_spec(dyn)),
        out_specs=_rep_spec(out_struct),
        check_vma=False)(cluster, batch, rng, dyn)


# --------------------------------------------------------------------------
# delta scatter


def _cluster_specs(cluster):
    """Per-field PartitionSpec tree of the resident cluster's committed
    layout (parallel/mesh.py shard_cluster): node-axis tensors over
    "nodes", existing-pod tensors over "pods", term/vocab pytrees
    replicated."""
    from .mesh import NODE_AXIS_FIELDS, POD_AXIS_FIELDS
    out = {}
    for f in type(cluster)._fields:
        v = getattr(cluster, f)
        if f in NODE_AXIS_FIELDS:
            out[f] = P(AXIS_NODES)
        elif f in POD_AXIS_FIELDS:
            out[f] = P(AXIS_PODS)
        else:
            out[f] = jax.tree.map(lambda _: P(), v)
    return type(cluster)(**out)


def _apply_delta_body(cluster, delta, mesh_key):
    mesh = _get_mesh(mesh_key)
    specs = _cluster_specs(cluster)

    def body(cl, d):
        # shift the replicated global row tables into THIS shard's local
        # row space; rows owned by other shards (and the one-past-
        # capacity pads) map one past the LOCAL capacity, which the
        # scatter's mode="drop" discards — the pre-sharded twin of the
        # single-device scatter, field math shared verbatim
        nl = cl.allocatable.shape[0]
        pl_ = cl.pod_valid.shape[0]
        noff = lax.axis_index(AXIS_NODES) * nl
        poff = lax.axis_index(AXIS_PODS) * pl_
        nr = d.node_rows - noff
        nr = jnp.where((nr >= 0) & (nr < nl), nr, nl)
        pr = d.pod_rows - poff
        pr = jnp.where((pr >= 0) & (pr < pl_), pr, pl_)
        return programs._apply_cluster_delta(
            cl, d._replace(node_rows=nr, pod_rows=pr))

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(specs, _rep_spec(delta)),
                         out_specs=specs, check_vma=False)(cluster, delta)


_shardmap_apply_delta_donated = jax.jit(
    _apply_delta_body, static_argnames=("mesh_key",), donate_argnums=(0,))
_shardmap_apply_delta_shared = jax.jit(
    _apply_delta_body, static_argnames=("mesh_key",))


def apply_cluster_delta_mesh(cluster, delta, mesh, donate: bool = True):
    """Pre-sharded resident scatter: apply a ClusterDelta to the sharded
    resident WITHOUT the legacy partitioner — each shard scatters its
    locally-owned rows (node AND pod axis).  Falls back to the GSPMD
    lowering when an axis does not divide the mesh (shard_map cannot
    pad); node-axis-only meshes divide trivially on the pod axis."""
    import jax.numpy as jnp  # noqa: F811 - local alias mirrors delta.py

    from . import mesh as pmesh
    mp = mesh.shape[AXIS_PODS]
    mn = mesh.shape[AXIS_NODES]
    n_nodes = int(cluster.allocatable.shape[0])
    n_pods = int(cluster.pod_valid.shape[0])
    if n_nodes % mn or n_pods % mp:
        return pmesh.sharded_apply_cluster_delta(cluster, delta, mesh,
                                                 donate=donate,
                                                 partitioner="gspmd")
    key = register_mesh(mesh)
    delta = pmesh.replicate(jax.tree.map(jnp.asarray, delta), mesh)
    fn = (_shardmap_apply_delta_donated if donate
          else _shardmap_apply_delta_shared)
    return fn(cluster, delta, mesh_key=key)


# --------------------------------------------------------------------------
# dispatch wrappers (the parallel/mesh.py sharded_* entries route here)


def schedule_gang_mesh(cluster, batch, cfg, rng, mesh,
                       shard_existing_pods: bool = True,
                       max_rounds: Optional[int] = None,
                       host_ok=None, intra_batch_topology: bool = True,
                       score_bias=None,
                       residual_window: int = 512) -> GangResult:
    """Gang auction over the mesh via the explicit shard_map program.
    Placement mirrors the GSPMD entry (shard_cluster/shard_batch commit
    the inputs); the AOT seam keys on (cfg, mesh_key, surface)."""
    from ..utils import aot
    from . import mesh as pmesh
    if cfg.percentage_of_nodes_to_score != 100:
        # the auction needs the global view; normalize the static out of
        # the program key exactly like gang.schedule_gang
        cfg = cfg._replace(percentage_of_nodes_to_score=100)
    n_nodes = int(cluster.allocatable.shape[0])
    n_pods = int(batch.valid.shape[0])
    surface = gang_surface(cfg, intra_batch_topology, batch, mesh,
                           n_nodes, n_pods)
    key = register_mesh(mesh)
    cluster = pmesh.shard_cluster(cluster, mesh, shard_existing_pods)
    batch = pmesh.shard_batch(batch, mesh)
    rng = pmesh._put(rng, NamedSharding(mesh, P()))
    host_ok = pmesh._shard_host_ok(host_ok, mesh)
    score_bias = pmesh._shard_host_ok(score_bias, mesh)
    with pmesh.ambient_mesh(mesh):
        return aot.dispatch(
            "_shardmap_gang", _shardmap_gang,
            (cluster, batch, cfg, rng),
            dict(mesh_key=key, host_ok=host_ok, score_bias=score_bias,
                 max_rounds=max_rounds,
                 intra_batch_topology=intra_batch_topology,
                 residual_window=residual_window, surface=surface),
            static_argnums=(2,),
            static_argnames=("mesh_key", "max_rounds",
                             "intra_batch_topology", "residual_window",
                             "surface"))


def schedule_sequential_mesh(cluster, batch, cfg, rng, mesh,
                             shard_existing_pods: bool = True,
                             hard_pod_affinity_weight: float = 1.0,
                             host_ok=None, start_index=0,
                             score_bias=None):
    """Sequential replay over the mesh via the explicit shard_map
    program (replicated scan body; see _shardmap_sequential)."""
    from ..utils import aot
    from . import mesh as pmesh
    key = register_mesh(mesh)
    cluster = pmesh.shard_cluster(cluster, mesh, shard_existing_pods)
    batch = pmesh.shard_batch(batch, mesh)
    rng = pmesh._put(rng, NamedSharding(mesh, P()))
    host_ok = pmesh._shard_host_ok(host_ok, mesh)
    score_bias = pmesh._shard_host_ok(score_bias, mesh)
    with pmesh.ambient_mesh(mesh):
        return aot.dispatch(
            "_shardmap_sequential", _shardmap_sequential,
            (cluster, batch, cfg, rng),
            dict(mesh_key=key,
                 hard_pod_affinity_weight=hard_pod_affinity_weight,
                 host_ok=host_ok, start_index=start_index,
                 score_bias=score_bias),
            static_argnums=(2,),
            static_argnames=("mesh_key",))
