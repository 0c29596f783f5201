"""Sharded-vs-unsharded equivalence on a virtual CPU mesh.

The driver separately dry-runs __graft_entry__.dryrun_multichip; this test
additionally checks numerical equivalence: the sharded program must
produce exactly the placements of the single-device program.

Two lowerings exist (parallel/mesh.py ``partitioner=``):

* ``shard_map`` (default, parallel/shardmap.py) — the explicit program
  with hand-placed collectives.  Exact on EVERY mesh shape, including
  the pod-axis (2, 4)/(4, 2) splits the legacy partitioner mis-lowers;
  the tests below assert it UNGATED.
* ``gspmd`` (legacy) — the derive-everything lowering, reachable only
  by argument.  It mis-lowered pod-axis splits on jax 0.4.x and its
  (2, 4) test was skipped there; on the installed jax 0.9.0 that test
  runs and passes, so it is asserted like the rest (ROADMAP C2 decides
  whether the old lowering stays at all).
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import __graft_entry__ as graft
from kubetpu.api import types as api
from kubetpu.models import programs
from kubetpu.models.gang import schedule_gang
from kubetpu.models.sequential import schedule_sequential
from kubetpu.parallel import mesh as pmesh
from kubetpu.parallel import shardmap
from tests.test_gang import build
from tests.test_tensors import mknode, mkpod

cpu_devices = jax.devices("cpu")
pytestmark = pytest.mark.skipif(len(cpu_devices) < 8,
                                reason="needs 8 virtual CPU devices")


def test_make_mesh_raises_on_a_shape_the_backend_cannot_satisfy():
    """No quiet detour onto another platform's devices: a shape the
    default backend's device set cannot fill is an error."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="needs %d devices" % (n + 1)):
        pmesh.make_mesh((1, n + 1))
    with pytest.raises(ValueError, match="needs 4 devices"):
        pmesh.make_mesh((2, 2), devices=cpu_devices[:3])
    assert pmesh.make_mesh((2, n // 2)).devices.shape == (2, n // 2)


def _inputs():
    cluster, batch, cfg = graft._example(n_nodes=32, n_pending=16)
    cpu0 = cpu_devices[0]
    cluster = jax.tree.map(lambda x: jax.device_put(x, cpu0), cluster)
    batch = jax.tree.map(lambda x: jax.device_put(np.asarray(x), cpu0), batch)
    rng = jax.device_put(jax.random.PRNGKey(7), cpu0)
    return cluster, batch, cfg, rng


def _assert_gang_equal(ref, res):
    for f in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(ref, f)), np.asarray(getattr(res, f)),
            err_msg=f"GangResult.{f} diverged sharded-vs-unsharded")


def test_sharded_batch_matches_single_device():
    cluster, batch, cfg, rng = _inputs()
    ref_res, ref_chosen = programs.schedule_batch(cluster, batch, cfg, rng)

    mesh = pmesh.make_mesh((2, 4), devices=cpu_devices[:8])
    res, chosen = pmesh.sharded_schedule_batch(cluster, batch, cfg, rng, mesh)

    np.testing.assert_array_equal(np.asarray(ref_res.feasible),
                                  np.asarray(res.feasible))
    np.testing.assert_allclose(np.asarray(ref_res.scores),
                               np.asarray(res.scores), rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(ref_chosen), np.asarray(chosen))


def test_sharded_gang_matches_single_device_node_axis():
    cluster, batch, cfg, rng = _inputs()
    ref = schedule_gang(cluster, batch, cfg, rng)

    mesh = pmesh.make_mesh((1, 8), devices=cpu_devices[:8])
    res = pmesh.sharded_schedule_gang(cluster, batch, cfg, rng, mesh)
    _assert_gang_equal(ref, res)


def test_sharded_gang_pod_axis_2d_shard_map():
    """The previously env-gated shape, through the shard_map program:
    pod-axis (2, 4) AND (4, 2) must reproduce the single-device
    GangResult bit-for-bit — every field, not just placements (this
    batch carries topology terms, so it exercises the replicated
    surface)."""
    cluster, batch, cfg, rng = _inputs()
    ref = schedule_gang(cluster, batch, cfg, rng)
    for shape in ((2, 4), (4, 2)):
        mesh = pmesh.make_mesh(shape, devices=cpu_devices[:8])
        res = pmesh.sharded_schedule_gang(cluster, batch, cfg, rng, mesh)
        _assert_gang_equal(ref, res)


def test_sharded_sequential_pod_axis_2d_shard_map():
    """Sequential at the previously env-gated pod-axis shapes: the
    shard_map scan replicates the serial program per device, so the
    legacy partitioner's chosen-row scaling fault cannot occur."""
    cluster, batch, cfg, rng = _inputs()
    ref = schedule_sequential(cluster, batch, cfg, rng)
    for shape in ((2, 4), (4, 2)):
        mesh = pmesh.make_mesh(shape, devices=cpu_devices[:8])
        res = pmesh.sharded_schedule_sequential(cluster, batch, cfg, rng,
                                                mesh)
        for f in ref._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(res, f)),
                err_msg=f"SeqResult.{f} diverged sharded-vs-unsharded "
                        f"at {shape}")


def _term_free_world(n_nodes=32, n_pods=16):
    """A term-free world (no pod topology terms, no controller spread
    selectors): the tiled shard_map surface."""
    from kubetpu.framework.types import NodeInfo, PodInfo
    from kubetpu.harness import hollow
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder

    nodes = hollow.make_nodes(n_nodes, zones=4)
    existing = hollow.make_pods(n_nodes, prefix="ex-", group_labels=8)
    infos = []
    for i, n in enumerate(nodes):
        ni = NodeInfo(n)
        p = existing[i]
        p.spec.node_name = n.name
        ni.add_pod(p)
        infos.append(ni)
    pending = hollow.make_pods(n_pods, prefix="pend-", group_labels=0)
    pinfos = [PodInfo(p) for p in pending]
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=programs.DEFAULT_FILTER_PLUGINS,
        scores=programs.DEFAULT_SCORE_PLUGINS,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0))
    return cluster, batch, cfg, jax.random.PRNGKey(3)


def test_sharded_gang_tiled_term_free():
    """The SCALE surface: a term-free batch routes to the tiled
    shard_map auction — gather-free one-hot selection with node-axis
    collectives and pods-axis all_gather resolution — and must be
    bit-identical to the lax oracle, both monolithic and through the
    windowed-residual (masked window) rounds."""
    cluster, batch, cfg, rng = _term_free_world()
    mesh = pmesh.make_mesh((2, 4), devices=cpu_devices[:8])
    assert shardmap.gang_surface(cfg, False, batch, mesh, 32,
                                 int(batch.valid.shape[0])) == "tiled"
    ref = schedule_gang(cluster, batch, cfg, rng,
                        intra_batch_topology=False)
    res = pmesh.sharded_schedule_gang(cluster, batch, cfg, rng, mesh,
                                      intra_batch_topology=False)
    _assert_gang_equal(ref, res)
    # windowed residual rounds (residual_window < B) use window MASKING
    # in the tiled body — same selected set, same admission order
    refw = schedule_gang(cluster, batch, cfg, rng,
                         intra_batch_topology=False, residual_window=4)
    resw = shardmap.schedule_gang_mesh(cluster, batch, cfg, rng, mesh,
                                       intra_batch_topology=False,
                                       residual_window=4)
    _assert_gang_equal(refw, resw)


# --------------------------------------------------------------------------
# the tiled auction against the single-device auction, differentially:
# build_bundle's cases (full default score family, hostPorts, taints,
# existing pods' preferred affinity in cluster.score_terms, a host score
# bias plane) on worlds the one plain world above does not reach

FULL_FILTERS = ("NodeUnschedulable", "NodeResourcesFit", "NodeName",
                "NodePorts", "NodeAffinity", "TaintToleration",
                "PodTopologySpread", "InterPodAffinity")


def churned_world(seed, n_nodes, n_pods):
    """Randomized churned world: heterogeneous capacities, zones, taints,
    unschedulable nodes, hostPort pods, tolerations, preferred NODE
    affinity, and existing pods carrying preferred POD affinity — the
    latter lands in cluster.score_terms, so the InterPodAffinity raw
    plane is genuinely nonzero (IPA coverage withOUT batch terms, which
    is exactly the tiled surface)."""
    r = random.Random(seed)
    nodes = []
    for i in range(n_nodes):
        labels = {"disk": r.choice(["ssd", "hdd"])}
        if r.random() < 0.8:
            labels[api.LABEL_ZONE] = "z%d" % r.randrange(3)
        taints = []
        if r.random() < 0.2:
            taints.append(api.Taint(
                key="dedicated", value="gpu",
                effect=r.choice(["NoSchedule", "PreferNoSchedule"])))
        nodes.append(mknode(name=f"n{i}", labels=labels,
                            cpu=r.choice(["2", "4", "8"]),
                            mem=r.choice(["4Gi", "16Gi"]),
                            pods=str(r.choice([4, 8, 110])),
                            taints=taints,
                            unschedulable=r.random() < 0.05))
    existing = {}
    for i in range(n_nodes):
        eps = []
        for j in range(r.randrange(0, 4)):
            p = mkpod(name=f"e{i}_{j}",
                      labels={"app": r.choice(["a", "b", "c"])},
                      cpu=r.choice(["100m", "500m"]), mem="128Mi")
            if r.random() < 0.3:
                p.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
                    preferred_during_scheduling_ignored_during_execution=[
                        api.WeightedPodAffinityTerm(
                            weight=r.choice([10, 50]),
                            pod_affinity_term=api.PodAffinityTerm(
                                label_selector=api.LabelSelector(
                                    match_labels={
                                        "app": r.choice(["a", "b"])}),
                                topology_key=api.LABEL_ZONE))]))
            eps.append(p)
        existing[f"n{i}"] = eps
    pending = []
    for i in range(n_pods):
        kw = {}
        if r.random() < 0.25:
            kw["tolerations"] = [api.Toleration(key="dedicated",
                                                operator="Exists")]
        p = mkpod(name=f"p{i}", labels={"app": r.choice(["a", "b", "c"])},
                  cpu=r.choice(["100m", "500m", "1"]),
                  mem=r.choice(["64Mi", "512Mi"]), **kw)
        if r.random() < 0.2:
            p.spec.containers[0].ports = [api.ContainerPort(
                container_port=8080, host_port=r.choice([8080, 9090]))]
        if r.random() < 0.15:
            p.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
                preferred_during_scheduling_ignored_during_execution=[
                    api.PreferredSchedulingTerm(
                        weight=r.choice([10, 100]),
                        preference=api.NodeSelectorTerm(match_expressions=[
                            api.NodeSelectorRequirement(
                                key="disk", operator="In",
                                values=["ssd"])]))]))
        pending.append(p)
    return build(nodes, existing, pending, filters=FULL_FILTERS,
                 scores=programs.DEFAULT_SCORE_PLUGINS)


def _tiled_vs_single(cluster, batch, cfg, rng, **kw):
    """(single-device auction, tiled shard_map auction) of one call on
    the (2, 4) mesh (both axes are pow2-bucketed from 8 up, so they
    divide); asserts the dispatch really took the tiled surface."""
    mesh = pmesh.make_mesh((2, 4), devices=cpu_devices[:8])
    assert shardmap.gang_surface(
        cfg, False, batch, mesh, int(cluster.allocatable.shape[0]),
        int(batch.valid.shape[0])) == "tiled"
    ref = schedule_gang(cluster, batch, cfg, rng,
                        intra_batch_topology=False, **kw)
    res = shardmap.schedule_gang_mesh(cluster, batch, cfg, rng, mesh,
                                      intra_batch_topology=False, **kw)
    return ref, res


def test_tiled_differential_contended_full_scores():
    """Contended auction (16 pods, 4 nodes) under the complete default
    score family: every GangResult field bit-matches."""
    nodes = [mknode(name=f"n{i}", cpu="2", pods="6") for i in range(4)]
    pending = [mkpod(name=f"p{i}", cpu="500m") for i in range(16)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    ref, res = _tiled_vs_single(cluster, batch, cfg, jax.random.PRNGKey(5))
    _assert_gang_equal(ref, res)
    assert int(ref.rounds) >= 2, "contention must force multiple rounds"


@pytest.mark.parametrize("seed,n_nodes,n_pods,rw", [
    (0, 3, 24, 4),      # deep windowed residual rounds
    (1, 150, 12, 0),    # wide node axis, monolithic loop
    (2, 9, 17, 512),    # window wider than batch == full-width rounds
    # three seeds of the former slow sweep, at its smaller sizes
    (3, 9, 5, 512),
    (4, 3, 40, 64),
    (5, 40, 40, 4),
])
def test_tiled_differential_randomized_property(seed, n_nodes, n_pods, rw):
    """Randomized churned clusters (ports/taints/zones/IPA score terms):
    the tiled and the single-device GangResults are bit-identical,
    across the windowed and monolithic round schedules."""
    cluster, batch, cfg, _ = churned_world(seed, n_nodes, n_pods)
    ref, res = _tiled_vs_single(cluster, batch, cfg,
                                jax.random.PRNGKey(seed),
                                residual_window=rw)
    _assert_gang_equal(ref, res)


def test_tiled_zero_feasible_pods_edge():
    """Every node unschedulable: the auction terminates after round 0
    with nothing placed, identically on both paths."""
    nodes = [mknode(name=f"n{i}", unschedulable=True) for i in range(4)]
    pending = [mkpod(name=f"p{i}") for i in range(8)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    ref, res = _tiled_vs_single(cluster, batch, cfg, jax.random.PRNGKey(1))
    _assert_gang_equal(ref, res)
    assert np.all(np.asarray(ref.chosen) == -1)


def test_tiled_score_bias_plane():
    """Host Score-plugin bias rides the tiled auction as a plane, applied
    after the plugin combine exactly like the single-device round."""
    nodes = [mknode(name=f"n{i}") for i in range(5)]
    pending = [mkpod(name=f"p{i}") for i in range(6)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    B, N = batch.valid.shape[0], cluster.allocatable.shape[0]
    bias = np.zeros((B, N), np.float32)
    bias[:, :5] = np.random.RandomState(3).rand(5)[None, :] * 7
    ref, res = _tiled_vs_single(cluster, batch, cfg, jax.random.PRNGKey(2),
                                score_bias=jnp.asarray(bias))
    _assert_gang_equal(ref, res)


def _surface(cfg, intra, batch, shape=(2, 4), n_nodes=32, n_pods=16):
    mesh = pmesh.make_mesh(shape, devices=cpu_devices[:shape[0] * shape[1]])
    return shardmap.gang_surface(cfg, intra, batch, mesh, n_nodes, n_pods)


def test_topology_batch_takes_the_replicated_surface():
    """A batch carrying required anti-affinity routes
    intra_batch_topology=True: the tiled auction freezes the pod axis,
    so the dispatch must take the replicated single-device body."""
    cluster, batch, cfg, _ = _term_free_world()
    assert _surface(cfg, False, batch) == "tiled"
    assert _surface(cfg, True, batch) == "replicated"


def test_soft_spread_batch_takes_the_replicated_surface():
    """The one content-dependent hole in the cfg-level gate: a batch
    whose pods carry ScheduleAnyway spread constraints must leave the
    tiled surface even under intra_batch_topology=False (its constant
    PodTopologySpread path would silently diverge from the real soft
    scoring)."""
    nodes = [mknode(name=f"n{i}", labels={api.LABEL_ZONE: f"z{i % 2}",
                                          api.LABEL_HOSTNAME: f"n{i}"})
             for i in range(4)]
    pending = [mkpod(name=f"p{i}", labels={"app": "a"}) for i in range(8)]
    for p in pending:
        p.spec.topology_spread_constraints = [api.TopologySpreadConstraint(
            max_skew=1, topology_key=api.LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            label_selector=api.LabelSelector(match_labels={"app": "a"}))]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    n_nodes = int(cluster.allocatable.shape[0])
    n_pods = int(batch.valid.shape[0])
    assert _surface(cfg, False, batch, shape=(1, 1), n_nodes=n_nodes,
                    n_pods=n_pods) == "replicated"
    plain = batch._replace(spread_soft=batch.spread_soft._replace(
        valid=np.zeros_like(batch.spread_soft.valid)))
    assert _surface(cfg, False, plain, shape=(1, 1), n_nodes=n_nodes,
                    n_pods=n_pods) == "tiled"


def test_unsupported_score_plugin_takes_the_replicated_surface():
    cluster, batch, cfg, _ = _term_free_world()
    odd = cfg._replace(scores=(("RequestedToCapacityRatio", 1),))
    assert _surface(odd, False, batch) == "replicated"
    assert _surface(cfg._replace(scores=programs.DEFAULT_SCORE_PLUGINS),
                    False, batch) == "tiled"


@pytest.mark.parametrize("n_nodes,n_pods", [(30, 16), (32, 15)])
def test_non_dividing_axis_takes_the_replicated_surface(n_nodes, n_pods):
    """shard_map does not pad: either sharded axis failing to divide the
    mesh leaves the tiled surface."""
    cluster, batch, cfg, _ = _term_free_world()
    assert _surface(cfg, False, batch, n_nodes=n_nodes,
                    n_pods=n_pods) == "replicated"


def test_sharded_gang_matches_single_device_gspmd_legacy():
    """The LEGACY gspmd lowering at (2, 4): this asserts the OLD
    partitioner, kept for comparison; the default shard_map path is
    covered above."""
    cluster, batch, cfg, rng = _inputs()
    ref = schedule_gang(cluster, batch, cfg, rng)

    mesh = pmesh.make_mesh((2, 4), devices=cpu_devices[:8])
    res = pmesh.sharded_schedule_gang(cluster, batch, cfg, rng, mesh,
                                      partitioner="gspmd")
    np.testing.assert_array_equal(np.asarray(ref.chosen), np.asarray(res.chosen))
    np.testing.assert_allclose(np.asarray(ref.requested),
                               np.asarray(res.requested), rtol=0, atol=0)


def test_sharded_sequential_matches_single_device():
    cluster, batch, cfg, rng = _inputs()
    ref = schedule_sequential(cluster, batch, cfg, rng)

    mesh = pmesh.make_mesh((1, 8), devices=cpu_devices[:8])
    res = pmesh.sharded_schedule_sequential(cluster, batch, cfg, rng, mesh)

    np.testing.assert_array_equal(np.asarray(ref.chosen), np.asarray(res.chosen))
    np.testing.assert_allclose(np.asarray(ref.requested),
                               np.asarray(res.requested), rtol=0, atol=0)


def _serve_outcomes(mesh_shape, mode, seed=7):
    """One scheduling cycle through the REAL serving path with the given
    mesh shape (None = single device); returns {pod name: node}."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler

    store = ClusterStore()
    for n in hollow.make_nodes(16, zones=4):
        store.add(n)
    pods = hollow.make_pods(24, group_labels=4)
    for i, p in enumerate(pods):
        if i % 3 == 0:
            hollow.with_spread(p, api.LABEL_ZONE, when="ScheduleAnyway")
        if i % 5 == 0:
            hollow.with_anti_affinity(p, api.LABEL_HOSTNAME)
        store.add(p)
    cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                     batch_size=32, mode=mode,
                                     mesh_shape=mesh_shape)
    sched = Scheduler(store, config=cfg, seed=seed, async_binding=False)
    out = sched.schedule_pending(timeout=0.0)
    sched.close()
    return {o.pod.metadata.name: o.node for o in out}


def test_serving_path_mesh_matches_single_device():
    """Scheduler honors mesh_shape: a (1,8) node-sharded mesh must produce
    EXACTLY the placements of the single-device run, in both execution
    modes (the mesh is a performance knob, never a semantics knob)."""
    for mode in ("sequential", "gang"):
        want = _serve_outcomes(None, mode)
        assert any(want.values())
        assert _serve_outcomes((1, 8), mode) == want


def test_serving_path_mesh_2d_matches_single_device():
    """The previously env-gated serving contract, now UNGATED through
    the shard_map path: a pod-axis (2, 4) mesh — topology batches, the
    double-buffered batch upload and the pre-sharded delta scatter
    included — produces exactly the single-device placements in both
    modes."""
    for mode in ("sequential", "gang"):
        want = _serve_outcomes(None, mode)
        assert any(want.values())
        assert _serve_outcomes((2, 4), mode) == want
