"""Compilation behavior: vocab growth within a pow2 bucket must reuse the
compiled program (the recompile-freedom SURVEY §7 asks for), and the
persistent cache is on by default in the serving path."""
import jax
import numpy as np

from kubetpu.api import types as api
from kubetpu.models import gang, programs
from kubetpu.models.batch import PodBatchBuilder
from kubetpu.framework.types import NodeInfo, PodInfo
from kubetpu.state.tensors import SnapshotBuilder
from tests.test_tensors import mknode, mkpod


def _world(n_label_values):
    nodes = [mknode(name=f"n{i}") for i in range(8)]
    infos = [NodeInfo(n) for n in nodes]
    pending = [mkpod(name=f"p{i}",
                     labels={"app": f"app-{i % n_label_values}"})
               for i in range(16)]
    sb = SnapshotBuilder()
    pinfos = [PodInfo(p) for p in pending]
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=("NodeResourcesFit",), scores=(),
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0))
    return cluster, batch, cfg


def test_no_recompile_within_vocab_bucket():
    """Interning a few more label values must keep every tensor inside its
    pow2 bucket, so the jitted program cache gains NO new entry — growth
    within a bucket is recompile-free."""
    c1, b1, cfg = _world(2)
    c2, b2, cfg2 = _world(5)
    # precondition: both worlds bucket to identical shapes (else this test
    # is probing the wrong thing)
    assert jax.tree.map(lambda x: x.shape, c1) == \
        jax.tree.map(lambda x: x.shape, c2)
    assert cfg == cfg2
    gang.schedule_gang(c1, b1, cfg, jax.random.PRNGKey(0))
    size1 = gang._schedule_gang._cache_size()
    res = gang.schedule_gang(c2, b2, cfg, jax.random.PRNGKey(1))
    assert gang._schedule_gang._cache_size() == size1
    assert (np.asarray(res.chosen)[:16] >= 0).all()


def test_serving_enables_persistent_cache(tmp_path):
    """Scheduler construction turns the persistent compilation cache on
    (warm restarts must not pay XLA again): at the fixed in-checkout path
    when nothing is configured, at the configured directory otherwise."""
    import kubetpu.utils.compilation as comp
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    prior = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        Scheduler(ClusterStore()).close()
        assert jax.config.jax_compilation_cache_dir == comp.DEFAULT_CACHE_DIR
        placed = str(tmp_path / "placed")
        jax.config.update("jax_compilation_cache_dir", placed)
        Scheduler(ClusterStore()).close()
        assert jax.config.jax_compilation_cache_dir == placed
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
