"""Pallas megakernel differential suite (ops/pallas_kernels.py).

The lax gang auction is the BIT-MATCH ORACLE: for every supported
(cfg, batch), ``kernel_backend="pallas"`` must reproduce the full
GangResult — placements, win scores, rounds, carries, diagnostics —
bit-for-bit.  Tier-1 runs the kernel under interpret=True on CPU;
Mosaic compilation is exercised on the chip by chip_smoke.py's Pallas
phase (and bench.py's backend_compare case).  Unsupported routings (topology batches, exotic score plugins)
must FALL BACK to lax with a recorded reason — and still be
bit-identical, trivially.
"""
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.models import gang, programs
from kubetpu.ops import pallas_kernels as PK
from kubetpu.utils import pallas_backend as PB
from tests.test_gang import build
from tests.test_tensors import mknode, mkpod

FULL_FILTERS = ("NodeUnschedulable", "NodeResourcesFit", "NodeName",
                "NodePorts", "NodeAffinity", "TaintToleration",
                "PodTopologySpread", "InterPodAffinity")


def _assert_bitmatch(a, b, ctx=""):
    for f in a._fields:
        av, bv = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert np.array_equal(av, bv), (
            f"{ctx}: GangResult.{f} diverged between lax and pallas "
            f"backends — the bit-match oracle contract is broken")


def _both(cluster, batch, cfg, rng, **kw):
    a = gang.schedule_gang(cluster, batch, cfg, rng,
                           intra_batch_topology=False, **kw)
    b = gang.schedule_gang(cluster, batch, cfg, rng,
                           intra_batch_topology=False,
                           kernel_backend="pallas", **kw)
    return a, b


def churned_world(seed, n_nodes, n_pods):
    """Randomized churned world: heterogeneous capacities, zones, taints,
    unschedulable nodes, hostPort pods, tolerations, preferred NODE
    affinity, and existing pods carrying preferred POD affinity — the
    latter lands in cluster.score_terms, so the kernel's InterPodAffinity
    raw plane is genuinely nonzero (IPA coverage withOUT batch terms,
    which is exactly the megakernel's supported surface)."""
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


def test_categorical_gumbel_decomposition():
    """The oracle's load-bearing identity: categorical(key, 0/-2**62
    logits) == argmax(where(tie, gumbel(key), -2**62)) BIT-EXACTLY — the
    kernel precomputes the gumbel rows instead of sampling in-kernel."""
    B, N = 64, 300
    rng = jax.random.PRNGKey(7)
    keys = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
        jnp.arange(B, dtype=jnp.int32))
    neg = jnp.float32(-2**62)
    rs = np.random.RandomState(0)
    scores = jnp.asarray(rs.randint(0, 5, size=(B, N)).astype(np.float32))
    feas = jnp.asarray(rs.rand(B, N) < 0.7)
    masked = jnp.where(feas, scores, neg)
    ties = (masked == jnp.max(masked, axis=1)[:, None]) & feas
    logits = jnp.where(ties, 0.0, neg)
    choice = jax.vmap(jax.random.categorical)(keys, logits)
    gum = jax.vmap(lambda k: jax.random.gumbel(k, (N,), jnp.float32))(keys)
    mine = jnp.argmax(jnp.where(ties, gum, neg), axis=1)
    np.testing.assert_array_equal(np.asarray(choice), np.asarray(mine))


def test_differential_contended_full_scores():
    """Contended auction (16 pods, 4 nodes) under the complete default
    score family: every GangResult field bit-matches, no fallback."""
    nodes = [mknode(name=f"n{i}", cpu="2", pods="6") for i in range(4)]
    pending = [mkpod(name=f"p{i}", cpu="500m") for i in range(16)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    PB.reset_fallbacks()
    a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(5))
    _assert_bitmatch(a, b, "contended")
    assert int(a.rounds) >= 2, "contention must force multiple rounds"
    assert PB.fallback_counts() == {}, "supported surface must not fall back"


@pytest.mark.parametrize("seed,n_nodes,n_pods,rw", [
    (0, 3, 24, 4),      # deep windowed residual rounds
    (1, 150, 12, 0),    # multi-node-tile (N > 128), monolithic loop
    (2, 9, 17, 512),    # window wider than batch == full-width rounds
])
def test_differential_randomized_property(seed, n_nodes, n_pods, rw):
    """Randomized churned clusters (ports/taints/zones/IPA score terms):
    lax and pallas-interpret GangResults are bit-identical, across the
    windowed and monolithic round schedules."""
    cluster, batch, cfg, _ = churned_world(seed, n_nodes, n_pods)
    a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(seed),
                 residual_window=rw)
    _assert_bitmatch(a, b, f"seed={seed}")


@pytest.mark.slow
def test_differential_randomized_property_broad():
    """The broader sweep (more seeds, bigger shapes incl. multi-pod-tile
    W > 128) — slow-marked; tier-1 runs the 3-case core above."""
    for seed in range(8):
        n_nodes = random.Random(seed * 7).choice([3, 9, 150, 200])
        n_pods = random.Random(seed * 13).choice([5, 40, 160])
        rw = random.Random(seed * 3).choice([0, 4, 64, 512])
        cluster, batch, cfg, _ = churned_world(seed, n_nodes, n_pods)
        a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(seed),
                     residual_window=rw)
        _assert_bitmatch(a, b, f"broad seed={seed}")


def test_zero_feasible_pods_edge():
    """Every node unschedulable: the auction terminates after the lax
    round 0 with nothing placed, identically on both backends."""
    nodes = [mknode(name=f"n{i}", unschedulable=True) for i in range(4)]
    pending = [mkpod(name=f"p{i}") for i in range(8)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(1))
    _assert_bitmatch(a, b, "zero-feasible")
    assert np.all(np.asarray(a.chosen) == -1)


def test_score_bias_plane():
    """Host Score-plugin bias rides the kernel as a plane, applied after
    the plugin combine exactly like the lax path."""
    nodes = [mknode(name=f"n{i}") for i in range(5)]
    pending = [mkpod(name=f"p{i}") for i in range(6)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    B, N = batch.valid.shape[0], cluster.allocatable.shape[0]
    bias = np.zeros((B, N), np.float32)
    bias[:, :5] = np.random.RandomState(3).rand(5)[None, :] * 7
    a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(2),
                 score_bias=jnp.asarray(bias))
    _assert_bitmatch(a, b, "score-bias")


def test_topology_batch_falls_back_with_reason():
    """A batch carrying required anti-affinity routes intra_batch_topology
    =True; kernel_backend='pallas' must fall back to lax (recorded
    reason) and produce the identical result."""
    nodes = [mknode(name=f"n{i}", labels={api.LABEL_ZONE: f"z{i % 2}"})
             for i in range(4)]
    pending = [mkpod(name=f"p{i}", labels={"app": "a"}) for i in range(6)]
    for p in pending:
        p.spec.affinity = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"app": "a"}),
                    topology_key=api.LABEL_ZONE)]))
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    PB.reset_fallbacks()
    rng = jax.random.PRNGKey(4)
    a = gang.schedule_gang(cluster, batch, cfg, rng)
    b = gang.schedule_gang(cluster, batch, cfg, rng,
                           kernel_backend="pallas")
    _assert_bitmatch(a, b, "topology-fallback")
    assert PB.fallback_counts().get("intra-batch-topology", 0) >= 1


def test_soft_spread_batch_falls_back_with_reason():
    """The one content-dependent hole in the cfg-level gate: a batch
    whose pods carry ScheduleAnyway spread constraints must fall back
    even under intra_batch_topology=False (the kernel's constant
    PodTopologySpread path would silently diverge from the lax path's
    real soft scoring) — and the results must still be identical via
    that fallback."""
    nodes = [mknode(name=f"n{i}", labels={api.LABEL_ZONE: f"z{i % 2}",
                                          api.LABEL_HOSTNAME: f"n{i}"})
             for i in range(4)]
    pending = [mkpod(name=f"p{i}", labels={"app": "a"}) for i in range(6)]
    for p in pending:
        p.spec.topology_spread_constraints = [api.TopologySpreadConstraint(
            max_skew=1, topology_key=api.LABEL_ZONE,
            when_unsatisfiable="ScheduleAnyway",
            label_selector=api.LabelSelector(match_labels={"app": "a"}))]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    assert PB.unsupported_reason(cfg, False, batch) == \
        "soft-spread-constraints"
    PB.reset_fallbacks()
    a, b = _both(cluster, batch, cfg, jax.random.PRNGKey(6))
    _assert_bitmatch(a, b, "soft-spread-fallback")
    assert PB.fallback_counts().get("soft-spread-constraints", 0) >= 1


def test_unsupported_score_plugin_falls_back():
    cfg = programs.ProgramConfig(
        scores=(("RequestedToCapacityRatio", 1),))
    assert PB.unsupported_reason(cfg, False) == \
        "score:RequestedToCapacityRatio"
    assert PB.unsupported_reason(cfg._replace(
        scores=programs.DEFAULT_SCORE_PLUGINS), False) is None
    assert PB.unsupported_reason(cfg, True) == "intra-batch-topology"


def test_aot_signature_keys_backends_distinct():
    """utils/aot.py seam: a pallas-backed executable must key distinctly
    from the lax build of the same call (kernel_backend is a static in
    the signature digest), so arming AOT can never serve a lax artifact
    to a pallas dispatch or vice versa."""
    from kubetpu.utils import aot
    nodes = [mknode(name=f"n{i}") for i in range(3)]
    pending = [mkpod(name=f"p{i}") for i in range(4)]
    cluster, batch, cfg, _ = build(nodes, {}, pending)
    args = (cluster, batch, cfg, jax.random.PRNGKey(0))
    keys = {}
    for backend in ("lax", "pallas"):
        key, _, _, _, _ = aot.call_signature(
            "_schedule_gang", gang._schedule_gang, args,
            dict(intra_batch_topology=False, kernel_backend=backend),
            static_argnums=(2,),
            static_argnames=("max_rounds", "intra_batch_topology",
                             "residual_window", "kernel_backend"))
        keys[backend] = key
    assert keys["lax"] != keys["pallas"]


def test_compile_once_per_bucket_watchdog():
    """Repeated pallas auctions at one shape bucket compile the fused
    program exactly once (rng content varies, shapes don't)."""
    from kubetpu.utils.sanitize import (install_compile_watchdog,
                                        uninstall_compile_watchdog)
    nodes = [mknode(name=f"n{i}", cpu="2", pods="8") for i in range(5)]
    pending = [mkpod(name=f"p{i}", cpu="500m") for i in range(12)]
    cluster, batch, cfg, _ = build(nodes, {}, pending, filters=FULL_FILTERS,
                                   scores=programs.DEFAULT_SCORE_PLUGINS)
    # warm everything once OUTSIDE the watchdog window
    gang.schedule_gang(cluster, batch, cfg, jax.random.PRNGKey(0),
                       intra_batch_topology=False,
                       kernel_backend="pallas").packed.block_until_ready()
    wd = install_compile_watchdog()
    try:
        for s in range(1, 4):
            r = gang.schedule_gang(cluster, batch, cfg,
                                   jax.random.PRNGKey(s),
                                   intra_batch_topology=False,
                                   kernel_backend="pallas")
            np.asarray(r.packed)
        gang_compiles = {k: c for k, c in wd.counts.items()
                         if "_schedule_gang" in k[0]}
        assert not gang_compiles, (
            "pallas auction recompiled within one shape bucket: "
            f"{gang_compiles}")
    finally:
        uninstall_compile_watchdog(wd)


def test_golden_worlds_backend_parity():
    """The committed placement-golden worlds, drained through the REAL
    Scheduler with kernel_backend pallas vs lax: placements identical.
    'basic' genuinely engages the megakernel (term-free pods); 'topology'
    exercises the per-cycle fallback routing."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.scheduler import Scheduler
    from tests.test_placement_goldens import WORLDS

    for world in ("basic", "topology"):
        results = {}
        for backend in ("lax", "pallas"):
            store, pods = WORLDS[world]()
            cfg = KubeSchedulerConfiguration(
                profiles=[KubeSchedulerProfile()], batch_size=100,
                mode="gang", chain_cycles=True, prewarm=False,
                kernel_backend=backend)
            sched = Scheduler(store, config=cfg, seed=0,
                              async_binding=False)
            for p in pods:
                store.add(p)
            out = []
            for _ in range(10):
                got = sched.schedule_pending(timeout=0.0)
                if not got:
                    break
                out.extend(got)
            sched.close()
            results[backend] = {o.pod.metadata.name: o.node for o in out}
        assert results["lax"] == results["pallas"], (
            f"{world}: scheduler-level placements diverged between "
            "kernel backends")
        assert results["lax"], f"{world}: nothing scheduled?"


def test_cycle_meta_records_backend_and_rounds():
    """Flight-recorder cycle meta carries auction_rounds + the EFFECTIVE
    kernel_backend, so traceview/bench can aggregate the round histogram
    and prove the megakernel actually engaged."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import trace as utrace

    fr = utrace.arm_flight_recorder()
    fr.clear()
    try:
        store = ClusterStore()
        for n in hollow.make_nodes(8, zones=2):
            store.add(n)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
            prewarm=False, kernel_backend="pallas")
        sched = Scheduler(store, config=cfg, async_binding=False)
        for p in hollow.make_pods(16, prefix="m-", group_labels=0):
            store.add(p)
        for _ in range(6):
            if not sched.schedule_pending(timeout=0.0):
                break
        sched.close()
        doc = fr.to_pipeline_doc(workload="test")
        metas = [c["meta"] for c in doc["cycle_meta"]
                 if c.get("meta", {}).get("auction_rounds") is not None]
        assert metas, "no gang cycle recorded auction_rounds meta"
        assert all(m["kernel_backend"] == "pallas" for m in metas), metas
        from tools.traceview import auction_summary
        line = auction_summary(doc)
        assert "auction rounds:" in line and "pallas" in line
    finally:
        utrace.disarm_flight_recorder()


def test_kernel_backend_config_decode_and_validate():
    from kubetpu.apis import load as cfgload
    cfg = cfgload.load_config({"mode": "gang", "kernelBackend": "pallas"})
    assert cfg.kernel_backend == "pallas"
    with pytest.raises(Exception):
        cfgload.load_config({"mode": "gang", "kernelBackend": "mosaic"})


def test_bench_rounds_hist():
    import bench
    assert bench._rounds_hist([1, 4, 4, 2, 4]) == {"1": 1, "2": 1, "4": 3}
    assert bench._rounds_hist([]) == {}
