"""What the term update says about itself (PR 29): the ``delta-terms``
span's args (the tables' live rows, their buckets, pods walked, owners
changed; since PR 47 the rows written, the rows free and whether a table
crossed whole), the ``delta-terms-upload`` span around the transfer and
dispatch of the written rows, and the ``(Et, Es)`` the auction ran with
on the cycle's meta.  Since PR 30 the update runs only when an owner came
or went: a kept cycle carries neither span and says ``terms_kept`` 1 on
its ``delta-build``; the journal, the replay rig and a pipelined drain
see the kept tables as they saw a cycle without owners.  Since PR 47 the
tables are kept by ROW: the journal captures the written rows, the replay
rig scatters them, and a steady drain runs one variant of that scatter."""

import copy
import pickle

import jax
import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.models import programs
from kubetpu.scheduler import Scheduler
from kubetpu.state.cache import SchedulerCache, Snapshot
from kubetpu.state.delta import DeltaTensorizer
from kubetpu.utils import journal as ujournal
from kubetpu.utils import sanitize
from kubetpu.utils import trace as utrace
from kubetpu.utils.journal import read_records
from tools.kubereplay import replay_journal

ARGS = {"filter_rows", "score_rows", "Et", "Es", "pods_walked",
        "owners_changed", "rows_written", "rows_free", "wholesale"}


def _owner(name, node="", preferred=False):
    """A pod that owns one term: required hostname anti-affinity to its
    own label, or (``preferred``) a weight-1 preferred affinity."""
    p = hollow.make_pod(name)
    p.metadata.labels = {"color": "red" if preferred else "green"}
    if preferred:
        p.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity())
        p.spec.affinity.pod_affinity \
            .preferred_during_scheduling_ignored_during_execution.append(
                api.WeightedPodAffinityTerm(
                    weight=1, pod_affinity_term=api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"color": "red"}),
                        topology_key=api.LABEL_HOSTNAME)))
    else:
        hollow.with_anti_affinity(p)
    p.spec.node_name = node
    return p


def _snapshot(cache):
    snap = Snapshot()
    cache.update_snapshot(snap)
    return snap.node_info_list


def test_a_refresh_says_what_it_rebuilt_and_how_many_owners_changed():
    cache = SchedulerCache()
    nodes = hollow.make_nodes(6, zones=3)
    for i, n in enumerate(nodes):
        cache.add_node(n)
        cache.add_pod(_owner(f"green-{i}", n.name))
        if i % 2:
            cache.add_pod(_owner(f"red-{i}", n.name, preferred=True))
    dt = DeltaTensorizer()
    _, st = dt.refresh(_snapshot(cache))
    assert st.resync and st.span_args == {}

    def refresh():
        _, st = dt.refresh(_snapshot(cache))
        assert not st.resync, st.reason
        names = [n for n, _, _ in st.spans]
        assert names == ["delta-build", "delta-terms", "delta-terms-upload",
                         "delta-apply"]
        # the upload lies inside the apply, after the refresh
        at = {n: (t0, t1) for n, t0, t1 in st.spans}
        assert at["delta-terms"][1] <= at["delta-terms-upload"][0] \
            <= at["delta-terms-upload"][1] <= at["delta-apply"][1]
        assert set(st.span_args) == {"delta-build", "delta-terms"}
        assert st.span_args["delta-build"]["terms_kept"] == 0
        assert set(st.span_args["delta-terms"]) == ARGS
        return st.span_args["delta-terms"]

    # a PLAIN pod lands on a node that holds a term owner: no owner
    # changed, so the tables are kept and nothing of the refresh runs
    plain = hollow.make_pod("plain-0")
    plain.spec.node_name = nodes[0].name
    cache.add_pod(plain)
    kept_terms = (dt.cluster.filter_terms, dt.cluster.score_terms)
    cluster, st = dt.refresh(_snapshot(cache), donate=False)
    assert not st.resync, st.reason
    assert [n for n, _, _ in st.spans] == ["delta-build", "delta-apply"]
    assert set(st.span_args) == {"delta-build"}
    assert st.span_args["delta-build"]["terms_kept"] == 1
    # undonated (an in-flight pipelined cycle still reads them), the kept
    # tables are the buffers the last build uploaded, still readable
    for was, now in zip(jax.tree.leaves(kept_terms),
                        jax.tree.leaves((cluster.filter_terms,
                                         cluster.score_terms))):
        assert not was.is_deleted()
        assert (was == now).all()
    # an owner arrives; an owner moves (one uid: counted once); one leaves
    extra = _owner("green-extra", nodes[1].name)
    cache.add_pod(extra)
    got = refresh()
    # walked: the arrival alone (until PR 47 the dirty node's three pods,
    # read for their owners).  Its one row is appended to the six live
    # ones, at the first of the build's two padding rows
    assert got == {"filter_rows": 7, "score_rows": 3, "Et": 8, "Es": 4,
                   "pods_walked": 1, "owners_changed": 1,
                   "rows_written": 1, "rows_free": 0, "wholesale": 0}
    cache.remove_pod(extra)
    extra.spec.node_name = nodes[2].name
    cache.add_pod(extra)
    got = refresh()
    # one uid, gone and come: its row tombstoned and taken again
    assert (got["owners_changed"], got["rows_written"], got["rows_free"],
            got["wholesale"]) == (1, 2, 0, 0)
    cache.remove_pod(extra)
    got = refresh()
    assert (got["filter_rows"], got["owners_changed"], got["rows_written"],
            got["rows_free"], got["Et"]) == (6, 1, 1, 1, 8)
    # nothing dirty: no refresh, nothing to say
    _, st = dt.refresh(_snapshot(cache))
    assert st.delta_rows == 0 and st.span_args == {}


def churn_on_three_residents(refresh_of):
    """Plain churn on four nodes of three residents each, then a Node
    relabelled in place and set again: what each refresh says, through
    ``refresh_of(cache)`` -> DeltaStats."""
    cache = SchedulerCache()
    nodes = hollow.make_nodes(4, zones=2)
    residents = {}
    for i, n in enumerate(nodes):
        n.metadata.labels["rack"] = f"rack-{i % 2}"
        cache.add_node(n)
        for k in range(3):
            p = hollow.make_pod(f"resident-{i}-{k}")
            p.spec.node_name = n.name
            cache.add_pod(p)
            residents[i, k] = p
    refresh = refresh_of(cache)
    assert refresh().resync

    def arrive(name, i):
        p = hollow.make_pod(name)
        p.spec.node_name = nodes[i].name
        cache.add_pod(p)
        return p
    first = arrive("arrival-0", 0)
    arrive("arrival-1", 1)
    yield refresh()
    cache.remove_pod(first)
    cache.remove_pod(residents[2, 1])
    arrive("arrival-2", 3)
    yield refresh()
    nodes[1].metadata.labels["rack"] = "rack-0"
    cache.update_node(nodes[1], nodes[1])
    arrive("arrival-3", 1)
    yield refresh()


# (delta_rows, delta_buckets) of the three refreshes, by hand: dirty
# nodes + the pod rows refilled or cleared (the two arrivals' rows; the
# two departed rows, one of them the next arrival's; the other freed row
# as the last arrival takes it).  Until PR 44 every pod row of a dirty
# node went to the device too: 2 + 8, 3 + 9 + 2 - 1 and 1 + 5 rows in
# buckets (8, 8), (8, 16), (8, 8).  The pod rows' bucket starts at four
# times the pending batch's (none here: 4 x 8)
CHANGED_ROWS = [(2 + 2, (8, 32)), (3 + 2, (8, 32)), (1 + 1, (8, 32))]


def test_a_delta_build_says_how_many_mirror_rows_it_refilled():
    """``pod_rows_refilled`` is the arrivals; ``pod_rows_seen`` the rows
    in the delta, those and the rows cleared; ``pods_walked`` the pods
    the host visited one at a time (no resident: no pod here owns a
    term); ``node_rows_refilled`` counts Nodes set again."""
    dt = DeltaTensorizer()
    stats = list(churn_on_three_residents(
        lambda cache: lambda: dt.refresh(_snapshot(cache))[1]))
    assert all(not st.resync for st in stats)
    assert [(st.delta_rows, st.delta_buckets) for st in stats] \
        == CHANGED_ROWS
    said = [{k: v for k, v in st.span_args["delta-build"].items()
             if k != "terms_kept"} for st in stats]
    assert said == [
        {"node_rows_dirty": 2, "node_rows_refilled": 0,
         "pod_rows_seen": 2, "pod_rows_refilled": 2, "pods_walked": 2},
        {"node_rows_dirty": 3, "node_rows_refilled": 0,
         "pod_rows_seen": 2, "pod_rows_refilled": 1, "pods_walked": 1},
        {"node_rows_dirty": 1, "node_rows_refilled": 1,
         "pod_rows_seen": 1, "pod_rows_refilled": 1, "pods_walked": 1}]


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _mixed_world(n_nodes=12):
    """Upstream's Mixed row in small: every node holds a green owner
    (required hostname anti-affinity) and a red one (preferred
    affinity), so every cycle's dirty nodes hold owners."""
    store = ClusterStore()
    nodes = hollow.make_nodes(n_nodes, zones=1)
    for i, n in enumerate(nodes):
        store.add(n)
        store.add(_owner(f"green-{i}", n.name))
        store.add(_owner(f"red-{i}", n.name, preferred=True))
    return store, nodes


def _mixed_scheduler(store, **kw):
    return Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang", **kw),
        async_binding=False)


def _drive(store, sched, pods, leaves=None):
    """One batch through the scheduler, an older pod leaving first: the
    departure is what takes the cycle off the chain and onto the delta
    path, as the benchmark's client does."""
    if leaves is not None:
        store.delete(store.get_pod("default", leaves))
    for p in pods:
        store.add(p)
    while sched.schedule_pending(timeout=0.0):
        pass


def _kept_rebuilt_kept(store, sched):
    """Plain traffic (kept), a labelled owner binds (its own cycle still
    kept: it is pending; the 38 bound pods outgrow the 32-row pod axis, so
    the kept tables ride a re-upload), the cycle after it (rebuilt), plain
    again (kept)."""
    _drive(store, sched, hollow.make_pods(8, prefix="warm-"))   # the resync
    _drive(store, sched, hollow.make_pods(8, prefix="plain-"),
           leaves="warm-0")
    _drive(store, sched, [_owner("red-new", preferred=True)],
           leaves="warm-1")
    _drive(store, sched, hollow.make_pods(8, prefix="later-"),
           leaves="warm-2")
    _drive(store, sched, hollow.make_pods(8, prefix="last-"),
           leaves="warm-3")


def test_the_cycle_record_carries_the_refreshs_args_and_the_term_buckets(
        flight):
    """Plain traffic on the small mixed world carries NO ``delta-terms``
    span (``terms_kept`` 1 on ``delta-build``, the buckets of the kept
    tables on the meta); the cycle after a labelled owner bound carries
    one, with ``owners_changed`` 1."""
    store, _ = _mixed_world()
    sched = _mixed_scheduler(store)
    try:
        _kept_rebuilt_kept(store, sched)
    finally:
        sched.close()
    records = [c.to_dict() for c in flight.cycles()]
    assert len(records) == 5
    assert [r["meta"]["resync"] for r in records] == [
        True, False, True, False, False]
    assert [e["args"]["reason"] for e in records[2]["events"]
            if e["name"] == "resync"] == ["pod-axis-growth"]

    def spans_of(record):
        return {s["name"]: s for s in record["spans"]}
    for record in (records[1], records[2], records[4]):
        spans = spans_of(record)
        assert not {"delta-terms", "delta-terms-upload"} & set(spans)
        assert spans["delta-build"]["args"]["terms_kept"] == 1
    assert records[1]["meta"]["term_buckets"] == [16, 16]
    spans = spans_of(records[3])
    assert spans["delta-build"]["args"]["terms_kept"] == 0
    # beside the tensorizer's other spans in the tree, inside the phase
    for name in ("delta-terms", "delta-terms-upload"):
        assert spans[name]["parent"] == spans["delta-build"]["parent"]
        assert spans["tensorize"]["t0"] <= spans[name]["t0"] \
            <= spans[name]["t1"] <= spans["tensorize"]["t1"]
    args = spans["delta-terms"]["args"]
    assert ARGS <= set(args)
    assert records[3]["meta"]["term_buckets"] == [args["Et"], args["Es"]]
    assert (args["filter_rows"], args["score_rows"], args["Et"], args["Es"],
            args["owners_changed"]) == (12, 13, 16, 16, 1)
    # the owner that came (a batch of one), not its node's three, not the
    # departure's node's and not the cluster's 38; its one score row
    assert args["pods_walked"] == 1
    assert (args["rows_written"], args["rows_free"], args["wholesale"]) \
        == (1, 0, 0)
    assert records[4]["meta"]["term_buckets"] == [16, 16]


def test_the_journal_captures_no_terms_on_a_kept_cycle_and_replays_it(
        tmp_path):
    """A kept cycle journals ``("delta", (delta, None))`` as a cycle
    without owners does, one that wrote term rows the ROWS it wrote (no
    table whole, one TermsDelta a table); kubereplay carries the resident
    tables over the kept records, scatters the rows and bit-matches the
    kept-rebuilt-kept window."""
    d = str(tmp_path / "journal")
    ujournal.disarm_journal()
    ujournal.arm_journal(d)
    store, _ = _mixed_world()
    sched = _mixed_scheduler(store)
    try:
        _kept_rebuilt_kept(store, sched)
    finally:
        sched.close()
        ujournal.disarm_journal()
    recs = [rec for _s, rec, _k in read_records(d)]
    assert [r["input"] for r in recs] == ["resync", "delta", "resync",
                                          "delta", "delta"]
    kept, rebuilt, last = (pickle.loads(recs[i]["input_payload"])[1]
                           for i in (1, 3, 4))
    assert kept is None and last is None
    whole, (fd, sd) = rebuilt
    assert whole == {}
    # the one score row of the owner that bound, at the table's first
    # padding row; nothing of the filter table
    Et = Es = 16
    assert not (fd.rows < Et).any()
    assert sd.rows[sd.rows < Es].tolist() == [12]
    assert sd.valid[:1].tolist() == [True] and not sd.valid[1:].any()
    rep = replay_journal(d)
    assert rep["replayed"] == rep["matched"] == 5 and rep["skipped"] == []
    assert rep["bit_match"] is True and rep["first_divergence"] is None


def test_a_pipelined_drain_over_owner_nodes_places_as_the_serial_one():
    """Depth 2, every node an owner's: a node update lands while a cycle
    is dispatched and uncommitted, so the next prepare's refresh KEEPS the
    tables with ``donate=False`` (the in-flight cycle's commit-side device
    work still reads them).  Label-only green pods in the stream are
    placed by the kept ``filter_terms`` alone; the drain places every pod
    where the serial drain does."""
    def drain(**kw):
        store, nodes = _mixed_world(n_nodes=8)
        # half the nodes hold no green owner: where a green label may go
        for i in range(0, 8, 2):
            store.delete(store.get_pod("default", f"green-{i}"))
        sched = _mixed_scheduler(store, **kw)
        seen = []
        orig = DeltaTensorizer.refresh

        def spy(self, node_infos, pending=(), donate=True, **kw):
            cluster, st = orig(self, node_infos, pending=pending,
                               donate=donate, **kw)
            kept = st.span_args.get("delta-build", {}).get("terms_kept")
            seen.append((donate, kept))
            return cluster, st
        DeltaTensorizer.refresh = spy
        try:
            pods = hollow.make_pods(40, prefix="pd-", cpu_milli=100)
            for i, p in enumerate(pods):
                if i % 5 == 0:
                    p.metadata.labels = {"color": "green"}
                store.add(p)
            out, flips = [], 0
            for _ in range(40):
                got = sched.schedule_pending(timeout=0.0)
                out.extend(got)
                if not got and not len(sched.queue):
                    break
                # a node update inside the vocab: no placement reads it,
                # the chain breaks and the node (an owner's) is dirty
                n = copy.deepcopy(nodes[flips % 8])
                n.metadata.labels["flap"] = "on"
                n.spec.unschedulable = False
                store.update(n)
                nodes[flips % 8] = n
                flips += 1
            out.extend(sched.flush_pipeline())
        finally:
            DeltaTensorizer.refresh = orig
            sched.close()
        placed = {o.pod.metadata.name: o.node for o in out}
        assert len(out) == len(placed) == 40 and all(placed.values())
        return placed, seen, store

    serial, seen, _ = drain(chain_cycles=True)
    assert (True, 1) in seen and not any(d is False for d, _ in seen)
    piped, seen, store = drain(chain_cycles=True, pipeline_cycles=True,
                               pipeline_depth=2)
    assert (False, 1) in seen, seen
    assert piped == serial
    # the kept filter table was live: no green label on a green owner's node
    for name, node in piped.items():
        pod = store.get_pod("default", name)
        if pod.metadata.labels.get("color") == "green":
            assert int(node.rsplit("-", 1)[1]) % 2 == 0, (name, node)


def _owner_churn(cache, nodes, rng, cycles):
    """Owners of both tables come and go, 1-4 a refresh, each table's
    count level: yields after each refresh's churn is in the cache."""
    live = []
    for k in range(6):
        p = _owner(f"seed-{k}", nodes[k % len(nodes)].name,
                   preferred=bool(k % 2))
        cache.add_pod(p)
        live.append(p)
    yield
    for c in range(cycles):
        gone = [live.pop(rng.randrange(len(live)))
                for _ in range(rng.randrange(1, 5))]
        for j, old in enumerate(gone):
            cache.remove_pod(old)
            p = _owner(f"churn-{c}-{j}", rng.choice(nodes).name,
                       preferred=old.spec.affinity.pod_affinity is not None)
            cache.add_pod(p)
            live.append(p)
        yield


def _term_leaves(cluster):
    return jax.tree.leaves((cluster.filter_terms, cluster.score_terms))


def test_the_journals_term_capture_is_a_row_delta_and_replays_onto_the_same_tables(
        tmp_path):
    """Armed, a refresh that wrote term rows captures ``(whole, deltas)``:
    the tables that crossed whole (none on a steady cycle) and one
    TermsDelta a table.  ``tools/kubereplay`` applies the captures in
    order to the journaled anchor and lands on the tensorizer's own
    resident tables leaf for leaf, the cycles that crossed a table whole
    (the bucket of rows outgrown; the first nil selector) included."""
    import random

    from tools.kubereplay import _apply_delta
    ujournal.disarm_journal()
    ujournal.arm_journal(str(tmp_path / "journal"))
    try:
        cache = SchedulerCache()
        nodes = hollow.make_nodes(4, zones=2)
        for n in nodes:
            cache.add_node(n)
        dt = DeltaTensorizer()
        _, st = dt.refresh(_snapshot(cache))
        kind, payload = dt.take_capture()
        assert kind == "resync"
        resident = pickle.loads(payload).to_device()
        wholes = rows = 0
        for _ in _owner_churn(cache, nodes, random.Random(47), 12):
            _, st = dt.refresh(_snapshot(cache))
            assert not st.resync, st.reason
            kind, payload = dt.take_capture()
            assert kind == "delta"
            whole, deltas = pickle.loads(payload)[1]
            assert set(whole) <= {"filter_terms", "score_terms"}
            assert bool(whole) == bool(
                st.span_args["delta-terms"]["wholesale"])
            wholes += bool(whole)
            # what the cycle sent: the rows it wrote, a row tombstoned and
            # taken again once
            sent = sum(int((d.rows < t.valid.shape[0]).sum())
                       for d, t in zip(deltas, (dt.cluster.filter_terms,
                                                dt.cluster.score_terms)))
            assert sent <= st.span_args["delta-terms"]["rows_written"]
            rows += sent
            resident = _apply_delta({"input_payload": payload}, resident)
            for a, b in zip(_term_leaves(resident),
                            _term_leaves(dt.cluster)):
                assert a.shape == b.shape and (a == b).all()
        # both ways were replayed: whole tables early, rows after
        assert 0 < wholes < 12 and rows > 0
    finally:
        ujournal.disarm_journal()


def test_a_steady_drain_runs_one_variant_of_the_term_scatter():
    """Thirty refreshes with 1-4 owners coming and as many going, the
    tables' shapes settled: the term scatter compiled ONCE, every refresh
    ran it (the rows' bucket stands on four times the batch's, as the pod
    rows' does), and no table crossed whole."""
    import random
    cache = SchedulerCache()
    nodes = hollow.make_nodes(4, zones=2)
    for n in nodes:
        cache.add_node(n)
    churn = _owner_churn(cache, nodes, random.Random(7), 30)
    with sanitize.sanitized() as wd:
        dt = DeltaTensorizer(resync_interval=1000)
        dt.refresh(_snapshot(cache))
        for k, _ in enumerate(churn):
            _, st = dt.refresh(_snapshot(cache))
            assert not st.resync, st.reason
            said = st.span_args["delta-terms"]
            # the seeds outgrow the empty build's one-row tables, once
            assert said["wholesale"] == (k == 0)
            assert said["rows_written"] >= 2
            assert "delta-terms-upload" in [n for n, _, _ in st.spans]
        compiles = {k: c for k, c in wd.counts.items()
                    if programs.TERMS_DELTA_PROGRAM in k[0]}
        assert sum(compiles.values()) == 1, compiles
        wd.assert_no_recompilation()
