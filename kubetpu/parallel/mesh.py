"""Device-mesh sharding of the scheduling program.

The reference scales one scheduling cycle with 16 chunked goroutines over the
node list (reference: pkg/scheduler/internal/parallelize/parallelism.go:26-43,
used from core/generic_scheduler.go:485 and framework.go:592).  The
TPU-native equivalent shards the dense tensors over a
`jax.sharding.Mesh` and lets XLA's SPMD partitioner insert the collectives
the goroutine fan-in/atomic-counter code did by hand:

  axis "pods"  — data parallelism over the pending-pod batch axis B (the
                 analog of running many scheduleOne loops at once) and over
                 the existing-pods axis P of the snapshot.
  axis "nodes" — the node axis N of every per-node array (the analog of the
                 16-goroutine chunking; also our "sequence parallelism" —
                 SURVEY.md §5: the reference's long axis IS node count).

Per-plugin NormalizeScore needs per-pod min/max over all nodes
(framework.go:613); under this sharding XLA lowers that to an all-reduce
over the "nodes" axis — the collective that replaces the serial
NormalizeScore loop.  Pair/topology segment-sums over sharded pod or node
axes become scatter-adds + psum.  Host code never writes collectives
explicitly; shardings are the whole parallel API, per the scaling-book
recipe (mesh -> annotate -> let XLA insert collectives).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import gang, programs, sequential
from ..state.tensors import ClusterTensors

AXIS_PODS = "pods"
AXIS_NODES = "nodes"


def ambient_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh for the
    enclosed dispatches (the inputs carry NamedShardings either way — the
    ambient mesh only backs mesh-less intermediates)."""
    return jax.set_mesh(mesh)


# ClusterTensors fields whose leading axis is the node axis N.
NODE_AXIS_FIELDS = frozenset({
    "allocatable", "requested", "nonzero_requested", "node_valid",
    "unschedulable", "kv", "keymask", "num", "topo_pair", "taints", "ports",
    "images", "avoid_hot", "zone_hot",
})
# ClusterTensors fields whose leading axis is the existing-pods axis P.
POD_AXIS_FIELDS = frozenset({
    "pod_kv", "pod_key", "pod_ns_hot", "pod_node", "pod_valid",
    "pod_terminating",
})


def make_mesh(shape: Optional[Tuple[int, int]] = None,
              devices=None) -> Mesh:
    """Build a ("pods", "nodes") mesh over ``devices`` (default: every
    device of the default backend).  Default shape puts all devices on
    the node axis (the reference's only intra-cycle parallel axis).  A
    shape the device set cannot satisfy raises: a mesh that quietly
    landed on another platform's devices would report results for
    hardware it never ran on."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    if shape is None:
        shape = (1, n)
    if shape[0] * shape[1] != n:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {shape[0] * shape[1]} "
            f"devices; the {devices[0].platform} backend has {n}")
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, (AXIS_PODS, AXIS_NODES))


def _put(x, sharding: NamedSharding):
    """device_put that also works on MULTI-PROCESS meshes: for
    non-fully-addressable shardings, build the global array from each
    process's addressable shards (device_put would run a cross-process
    same-value assert that trips on NaN padding — NaN != NaN).

    Arrays already committed to the requested sharding pass through
    untouched — the delta-maintained resident cluster
    (state/delta.py DeltaTensorizer with a mesh) re-enters
    shard_cluster every dispatch, and re-``device_put``-ing the whole
    [N, R] tensors each cycle was exactly the host cost the delta
    pipeline removes."""
    if isinstance(x, jax.Array) and x.sharding == sharding:
        return x
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(x, sharding)
    arr = np.asarray(x)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def shard_cluster(cluster: ClusterTensors, mesh: Mesh,
                  shard_existing_pods: bool = True) -> ClusterTensors:
    """device_put a host/replicated ClusterTensors onto the mesh."""
    out = {}
    for field in ClusterTensors._fields:
        val = getattr(cluster, field)
        if field in NODE_AXIS_FIELDS:
            out[field] = _put(val, NamedSharding(mesh, P(AXIS_NODES)))
        elif field in POD_AXIS_FIELDS and shard_existing_pods:
            out[field] = _put(val, NamedSharding(mesh, P(AXIS_PODS)))
        else:
            out[field] = jax.tree.map(
                lambda x: _put(x, NamedSharding(mesh, P())), val)
    return ClusterTensors(**out)


def shard_batch(batch, mesh: Mesh):
    """Shard every PodBatch leaf on dim 0 over the "pods" axis.  All batch
    leaves lead with B or a flattened B*T axis, so dim-0 sharding is the
    data-parallel split of the pending-pod batch.  Leaves that are
    already jax Arrays pass through without a host round-trip (the
    double-buffered upload path hands an ALREADY-SHARDED batch back in
    at dispatch — np.asarray here would read every leaf back to the
    host just to re-upload it)."""
    n = mesh.shape[AXIS_PODS]

    def put(x):
        if not isinstance(x, jax.Array):
            x = np.asarray(x)
        if x.ndim >= 1 and x.shape[0] % n == 0:
            return _put(x, NamedSharding(mesh, P(AXIS_PODS)))
        return _put(x, NamedSharding(mesh, P()))
    return jax.tree.map(put, batch)


def replicate(tree, mesh: Mesh):
    return jax.tree.map(
        lambda x: _put(x, NamedSharding(mesh, P())), tree)


def sharded_apply_cluster_delta(cluster, delta, mesh: Mesh,
                                donate: bool = True,
                                partitioner: Optional[str] = None):
    """Apply a ClusterDelta to the SHARDED resident cluster, shard-locally:
    the [D]-indexed update tables are tiny and ride replicated, and each
    shard scatters only its locally-owned rows — no shard ever
    re-materializes (or re-uploads) the full [N, R] / [P, L] tensors.
    The cluster keeps its committed shardings, so the next dispatch's
    shard_cluster is a pass-through.

    Default lowering is the EXPLICIT shard_map scatter
    (parallel/shardmap.py apply_cluster_delta_mesh — required for
    pod-axis sharded residents, where the legacy SPMD partitioner
    mis-lowers cross-shard index selection); ``partitioner="gspmd"``
    keeps the old ambient-mesh lowering for comparison/regression use."""
    if (partitioner or "shard_map") == "gspmd":
        from ..models import programs
        delta = replicate(jax.tree.map(np.asarray, delta), mesh)
        with ambient_mesh(mesh):
            return programs.apply_cluster_delta(cluster, delta,
                                                donate=donate)
    from . import shardmap
    return shardmap.apply_cluster_delta_mesh(cluster, delta, mesh,
                                             donate=donate)


def sharded_schedule_batch(cluster, batch, cfg: programs.ProgramConfig, rng,
                           mesh: Mesh, shard_existing_pods: bool = True):
    """One-shot batch scheduling over the mesh.  Inputs are placed with
    shard_cluster/shard_batch; jit consumes the committed shardings and the
    SPMD partitioner derives every intermediate sharding + collective."""
    cluster = shard_cluster(cluster, mesh, shard_existing_pods)
    batch = shard_batch(batch, mesh)
    rng = _put(rng, NamedSharding(mesh, P()))
    with ambient_mesh(mesh):
        return programs.schedule_batch(cluster, batch, cfg, rng)


def sharded_filter_and_score(cluster, batch, cfg: programs.ProgramConfig,
                             mesh: Mesh, host_ok=None,
                             shard_existing_pods: bool = True):
    """filter_and_score over the mesh (the extender path's device half)."""
    cluster = shard_cluster(cluster, mesh, shard_existing_pods)
    batch = shard_batch(batch, mesh)
    with ambient_mesh(mesh):
        return programs.filter_and_score(cluster, batch, cfg,
                                         host_ok=_shard_host_ok(host_ok,
                                                                mesh))


def _shard_host_ok(host_ok, mesh: Mesh):
    if host_ok is None:
        return None
    host_ok = np.asarray(host_ok)
    ok = (host_ok.shape[0] % mesh.shape[AXIS_PODS] == 0
          and host_ok.shape[1] % mesh.shape[AXIS_NODES] == 0)
    spec = P(AXIS_PODS, AXIS_NODES) if ok else P()
    return _put(host_ok, NamedSharding(mesh, spec))


def sharded_schedule_gang(cluster, batch, cfg: programs.ProgramConfig, rng,
                          mesh: Mesh, shard_existing_pods: bool = True,
                          max_rounds: Optional[int] = None,
                          host_ok=None, intra_batch_topology: bool = True,
                          score_bias=None,
                          partitioner: Optional[str] = None):
    """Gang auction over the mesh.  Default lowering is the EXPLICIT
    shard_map auction (parallel/shardmap.py): the [B, N] filter/score
    work shards over both axes, per-pod winners resolve via node-axis
    collectives + a pods-axis all_gather, and admission runs replicated
    — correct on pod-axis (2, 4)/(4, 2) meshes where the legacy SPMD
    partitioner mis-lowers the loop machinery (PR 6 skip markers).
    ``partitioner="gspmd"`` keeps the old derive-everything lowering,
    exact on node-axis (1, N) meshes only."""
    if (partitioner or "shard_map") == "gspmd":
        cluster = shard_cluster(cluster, mesh, shard_existing_pods)
        batch = shard_batch(batch, mesh)
        rng = _put(rng, NamedSharding(mesh, P()))
        with ambient_mesh(mesh):
            return gang.schedule_gang(
                cluster, batch, cfg, rng,
                host_ok=_shard_host_ok(host_ok, mesh),
                max_rounds=max_rounds,
                intra_batch_topology=intra_batch_topology,
                score_bias=_shard_host_ok(score_bias, mesh))
    from . import shardmap
    return shardmap.schedule_gang_mesh(
        cluster, batch, cfg, rng, mesh,
        shard_existing_pods=shard_existing_pods, max_rounds=max_rounds,
        host_ok=host_ok, intra_batch_topology=intra_batch_topology,
        score_bias=score_bias)


def sharded_schedule_sequential(cluster, batch, cfg: programs.ProgramConfig,
                                rng, mesh: Mesh,
                                shard_existing_pods: bool = True,
                                hard_pod_affinity_weight: float = 1.0,
                                host_ok=None, start_index=0,
                                score_bias=None,
                                partitioner: Optional[str] = None):
    """Sequential-replay scan over the mesh.  Default lowering is the
    explicit shard_map program (parallel/shardmap.py): the scan axis
    (pods, in order) is serial by construction, so the per-device body
    replicates the exact single-device scan — the correctness fix for
    the legacy partitioner's cross-shard index selection on pod-axis
    meshes.  ``partitioner="gspmd"`` keeps the old lowering (exact on
    node-axis (1, N) meshes only)."""
    if (partitioner or "shard_map") == "gspmd":
        cluster = shard_cluster(cluster, mesh, shard_existing_pods)
        batch = shard_batch(batch, mesh)
        rng = _put(rng, NamedSharding(mesh, P()))
        with ambient_mesh(mesh):
            return sequential.schedule_sequential(
                cluster, batch, cfg, rng,
                hard_pod_affinity_weight=hard_pod_affinity_weight,
                host_ok=_shard_host_ok(host_ok, mesh),
                start_index=start_index,
                score_bias=_shard_host_ok(score_bias, mesh))
    from . import shardmap
    return shardmap.schedule_sequential_mesh(
        cluster, batch, cfg, rng, mesh,
        shard_existing_pods=shard_existing_pods,
        hard_pod_affinity_weight=hard_pod_affinity_weight,
        host_ok=host_ok, start_index=start_index, score_bias=score_bias)
