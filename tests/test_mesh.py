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
import jax
import numpy as np
import pytest

import __graft_entry__ as graft
from kubetpu.api import types as api
from kubetpu.models import programs
from kubetpu.models.gang import schedule_gang
from kubetpu.models.sequential import schedule_sequential
from kubetpu.parallel import mesh as pmesh

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
    selectors): the tiled shard_map surface — the same supported
    surface as the Pallas megakernel."""
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
    from kubetpu.parallel import shardmap

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
