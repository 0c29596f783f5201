"""The batch's own score-side terms inside the gang auction (PR 40):
``models/gang.py`` ``_extend_cluster`` splices the term sets that
``ProgramConfig.batch_score_sets`` names into ``score_terms`` with owner
rows P + j, through the helper the chained materialize uses; a batch
that names none appends no row; ``GangResult.capacity_deferred`` counts
the proposals a round's admission refused."""

import jax
import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.framework.types import NodeInfo, PodInfo
from kubetpu.harness import hollow
from kubetpu.models import gang, programs
from kubetpu.models.batch import (PodBatchBuilder, batch_score_sets,
                                  densify_for, live_term_sets,
                                  score_rows_spliced)
from kubetpu.state.tensors import SnapshotBuilder

RED = {"color": "red"}


def _term(key=api.LABEL_HOSTNAME, labels=RED):
    return api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(labels)),
        topology_key=key)


def _pod(name, prefer=0, avoid=0, require=False, unknown_key=False):
    """A red pod with a preferred affinity term (weight ``prefer``), a
    preferred anti-affinity term (weight ``avoid``) and / or a required
    affinity term, all on the hostname key and selecting red pods."""
    p = hollow.make_pod(name, labels=RED)
    aff, anti = api.PodAffinity(), api.PodAntiAffinity()
    key = "no/such-key" if unknown_key else api.LABEL_HOSTNAME
    if prefer:
        aff.preferred_during_scheduling_ignored_during_execution.append(
            api.WeightedPodAffinityTerm(weight=prefer,
                                        pod_affinity_term=_term(key)))
    if require:
        aff.required_during_scheduling_ignored_during_execution.append(
            _term())
    if avoid:
        anti.preferred_during_scheduling_ignored_during_execution.append(
            api.WeightedPodAffinityTerm(weight=avoid,
                                        pod_affinity_term=_term()))
    if prefer or avoid or require:
        p.spec.affinity = api.Affinity(pod_affinity=aff,
                                       pod_anti_affinity=anti)
    return p


def _world(pending, n_nodes=8, bound=None):
    """(cluster, batch, cfg): ``n_nodes`` nodes, one bound pod on each of
    the first ``len(bound)``, the ``pending`` pods as one batch."""
    nodes = hollow.make_nodes(n_nodes, zones=2)
    bound = bound if bound is not None else [
        _pod(f"ex-{i}", prefer=1) for i in range(n_nodes)]
    infos = []
    for i, n in enumerate(nodes):
        ni = NodeInfo(n)
        if i < len(bound):
            bound[i].spec.node_name = n.name
            ni.add_pod(bound[i])
        infos.append(ni)
    pinfos = [PodInfo(p) for p in pending]
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=programs.DEFAULT_FILTER_PLUGINS,
        scores=programs.DEFAULT_SCORE_PLUGINS,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0))
    return cluster, batch, cfg


def test_the_host_names_the_sets_a_batch_has_rows_of():
    _, batch, _ = _world([_pod("a", prefer=2), _pod("b", require=True)])
    live = live_term_sets(batch)
    assert "pref" in live and "ra" in live
    assert batch_score_sets(live, 1) == ("pref", "ra")
    assert batch_score_sets(live, 0) == ("pref",)
    assert score_rows_spliced(batch, ("pref", "ra")) == 2
    _, plain, _ = _world([hollow.make_pod("p")])
    assert batch_score_sets(live_term_sets(plain)) == ()
    # a term on a key no node carries is interned with the batch: a row
    # on the host as in the program, whose owner pins no pair
    _, odd, _ = _world([_pod("c", prefer=1, unknown_key=True)])
    assert score_rows_spliced(odd, ("pref",)) == 1


def test_a_batch_that_names_no_set_appends_no_row():
    cluster, batch, _ = _world([_pod("a", prefer=1)])
    batch = densify_for(cluster, batch)
    ext = gang._extend_cluster(cluster, batch)
    assert ext.score_terms is cluster.score_terms
    P, B = cluster.pod_valid.shape[0], batch.valid.shape[0]
    assert ext.pod_valid.shape[0] == P + B
    assert ext.filter_terms.valid.shape[0] \
        == cluster.filter_terms.valid.shape[0] + B * batch.raa.valid.shape[1]


def test_the_spliced_rows_are_a_bound_pods_rows_sign_and_hard_weight():
    pending = [_pod("a", prefer=2, avoid=3), _pod("b", require=True),
               hollow.make_pod("c", labels=RED)]
    cluster, batch, _ = _world(pending)
    batch = densify_for(cluster, batch)
    P, B = cluster.pod_valid.shape[0], batch.valid.shape[0]
    Es = cluster.score_terms.valid.shape[0]
    Tp, Tr = batch.pref.valid.shape[1], batch.ra.valid.shape[1]
    assert Tp == 2 and Tr == 1
    ext = gang._extend_cluster(cluster, batch, ("pref", "ra"), 5.0)
    st = ext.score_terms
    assert st.valid.shape[0] == Es + B * Tp + B * Tr
    for f in ("topo_key", "pod_idx", "weight", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(st, f))[:Es],
                                      np.asarray(getattr(
                                          cluster.score_terms, f)))
    pref = slice(Es, Es + B * Tp)
    ra = slice(Es + B * Tp, None)
    # pod a's two preferred rows: +2 (affinity), -3 (anti-affinity)
    assert np.asarray(st.weight)[pref][:2].tolist() == [2.0, -3.0]
    assert np.asarray(st.valid)[pref].tolist() == [True, True] \
        + [False] * (B * Tp - 2)
    assert np.asarray(st.pod_idx)[pref].tolist() == [
        P + j for j in range(B) for _ in range(Tp)]
    # pod b's required-affinity row at hardPodAffinityWeight
    assert np.asarray(st.weight)[ra].tolist() == [0.0, 5.0] \
        + [0.0] * (B * Tr - 2)
    assert np.asarray(st.valid)[ra].tolist() == [False, True] \
        + [False] * (B * Tr - 2)
    assert np.asarray(st.pod_idx)[ra].tolist() == [P + j for j in range(B)]
    # the chained materialize makes the same rows (one helper)
    chosen = np.full((B,), -1, np.int32)
    chosen[:3] = [0, 1, 2]
    chain = gang.materialize_assigned(
        cluster, batch, chosen, cluster.requested,
        cluster.nonzero_requested,
        np.zeros((cluster.allocatable.shape[0], batch.ports_hot.shape[1]),
                 np.float32),
        extend_score_terms=True, hard_pod_affinity_weight=5.0)
    for f in ("topo_key", "pod_idx", "weight", "valid"):
        np.testing.assert_array_equal(np.asarray(getattr(st, f)),
                                      np.asarray(getattr(
                                          chain.score_terms, f)))


def test_naming_sets_a_batch_has_no_row_of_changes_no_placement():
    pending = [hollow.make_pod(f"p{i}", labels=RED) for i in range(6)]
    cluster, batch, cfg = _world(pending)
    rng = jax.random.PRNGKey(40)
    plain = gang.schedule_gang(cluster, batch, cfg, rng)
    named = gang.schedule_gang(
        cluster, batch, cfg._replace(batch_score_sets=("pref", "ra")), rng)
    for f in ("chosen", "score", "rounds", "packed", "capacity_deferred"):
        np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                      np.asarray(getattr(named, f)))


@pytest.mark.parametrize("window,refused", [(0, 5 + 2), (4, 5 + 1)])
def test_capacity_deferred_counts_what_admission_refused(window, refused):
    """Two nodes with room for three pods each by pod count and eight
    pods that all prefer node-0's red pod: round 1 sends all eight there,
    three fit, five are refused; round 2 sends the five to node-1, three
    fit, two are refused and stay unschedulable.  Under a residual window
    of four only four of the five propose in round 2, so one is refused:
    the count is of PROPOSALS, and a pod outside the window makes none."""
    nodes = hollow.make_nodes(2)
    for n in nodes:
        n.status.allocatable["pods"] = "4"
        n.status.capacity["pods"] = "4"
    infos = [NodeInfo(n) for n in nodes]
    for i, ni in enumerate(infos):
        ex = _pod(f"ex-{i}", prefer=1) if i == 0 else hollow.make_pod("ex-1")
        ex.spec.node_name = nodes[i].name
        ni.add_pod(ex)
    pinfos = [PodInfo(_pod(f"p{i}", prefer=1)) for i in range(8)]
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=programs.DEFAULT_FILTER_PLUGINS,
        scores=programs.DEFAULT_SCORE_PLUGINS,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0),
        batch_score_sets=("pref",))
    res = gang.schedule_gang(cluster, batch, cfg, jax.random.PRNGKey(1),
                             residual_window=window)
    chosen = np.asarray(res.chosen)[:8].tolist()
    assert chosen == [0, 0, 0, 1, 1, 1, -1, -1]
    assert int(res.capacity_deferred) == refused
    assert res.capacity_deferred.dtype == np.int32
    # not part of the one readback the serving loop makes
    assert res.packed.shape == (3 * batch.valid.shape[0] + 1,)


def test_a_journaled_drain_of_preferring_pods_replays_bit_identical(tmp_path):
    """The splice is keyed by the cycle's ProgramConfig, which the journal
    records whole: kubereplay re-runs every cycle of a drain whose pods
    prefer each other, on nodes that fill, to the same packed vector."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import journal as ujournal
    from kubetpu.utils.journal import read_records
    from tools.kubereplay import replay_journal
    jdir = str(tmp_path / "journal")
    ujournal.disarm_journal()
    ujournal.arm_journal(jdir)
    store = ClusterStore()
    for i in range(6):
        store.add(hollow.make_node(f"jn-{i}", cpu_milli=1000))
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=16, mode="gang"),
        async_binding=False)
    try:
        for i in range(40):
            p = _pod(f"jp-{i}", prefer=1)
            p.spec.containers[0].resources.requests["cpu"] = "100m"
            store.add(p)
        while sched.schedule_pending(timeout=0.0):
            pass
    finally:
        sched.close()
        ujournal.disarm_journal()
    recs = [rec for _s, rec, _k in read_records(jdir)]
    assert len(recs) >= 3
    assert all(r["cfg"].batch_score_sets == ("pref",) for r in recs)
    assert max(r["rounds"] for r in recs) >= 2      # nodes of ten filled
    rep = replay_journal(jdir)
    assert rep["replayed"] == rep["matched"] == len(recs)
    assert rep["bit_match"] is True
