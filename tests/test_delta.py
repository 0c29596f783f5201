"""Incremental delta-tensorization suite (state/delta.py, `make delta-test`):

  * golden equivalence — after randomized commit/evict/update sequences,
    the delta-applied device ClusterTensors bit-match a from-scratch
    ``SnapshotBuilder.build()`` of the same NodeInfos against the same
    InternTable, up to the documented stable-row permutation of the
    existing-pod axis (fresh builds pack pods in node-walk order; the
    delta path keeps rows stable and reuses freed rows lowest-first) and
    the term tables as multisets of valid rows beside padding rows;
  * fallback triggers — intern-table growth, term-carrying pod churn,
    node-set changes and pod-axis exhaustion all take the blessed resync
    path and still land on golden state;
  * the zero-delta chain case — an unchanged snapshot returns the SAME
    resident cluster object with delta_rows == 0;
  * compile-once watchdog — a 50-cycle delta drain compiles the scatter
    program at most once per pow2 bucket (utils/sanitize.py);
  * the serving loop — a multi-cycle gang drain with chaining OFF runs
    ONE full build (the initial resync) and scatters the rest.
"""

import collections
import copy
import random
import time

import jax
import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.harness import hollow
from kubetpu.state.cache import SchedulerCache, Snapshot
from kubetpu.state.delta import DeltaTensorizer
from kubetpu.state.tensors import SnapshotBuilder

NODE_AXIS_AND_VOCAB = [
    "allocatable", "requested", "nonzero_requested", "node_valid",
    "unschedulable", "kv", "keymask", "num", "topo_pair", "taints",
    "ports", "images", "avoid_hot", "zone_hot", "taint_is_hard",
    "taint_is_prefer", "image_size", "image_spread"]
POD_AXIS = ["pod_kv", "pod_key", "pod_ns_hot", "pod_node", "pod_valid",
            "pod_terminating"]


def snapshot_of(cache):
    snap = Snapshot()
    cache.update_snapshot(snap)
    return snap.node_info_list


def assert_matches_fresh(dt: DeltaTensorizer, node_infos) -> None:
    """The golden assertion: the resident device tensors equal a fresh
    build() against a COPY of the persistent intern table (ids fixed),
    bit-for-bit — node axis directly, pod axis under the uid-row
    permutation, remaining delta rows at build defaults; the term tables
    as canonical multisets of rows (``term_rows``), every other row a
    padding row."""
    fresh_b = SnapshotBuilder(
        table=copy.deepcopy(dt.builder.table),
        hard_pod_affinity_weight=dt.hard_pod_affinity_weight)
    fresh_host = fresh_b.build(node_infos)
    fresh = fresh_host.to_device()
    got = dt.cluster
    for f in NODE_AXIS_AND_VOCAB:
        a, b = np.asarray(getattr(got, f)), np.asarray(getattr(fresh, f))
        assert a.shape == b.shape, (f, a.shape, b.shape)
        assert np.array_equal(a, b), (
            f, np.argwhere(a != b)[:5] if a.shape == b.shape else None)
    drow, frow = dt.pod_row, fresh_host.arrays["_pod_rows"]
    assert set(drow) == set(frow)
    gotp = {f: np.asarray(getattr(got, f)) for f in POD_AXIS}
    frep = {f: np.asarray(getattr(fresh, f)) for f in POD_AXIS}
    for uid in drow:
        for f in POD_AXIS:
            assert np.array_equal(gotp[f][drow[uid]], frep[f][frow[uid]]), (
                uid, f)
    used = set(drow.values())
    for r in range(gotp["pod_valid"].shape[0]):
        if r not in used:
            assert not gotp["pod_valid"][r], r
            assert gotp["pod_node"][r] == -1, r
    # term tensors: the delta path keeps them by ROW (an owner's rows stay
    # where they were put, a departed owner's are tombstoned), a build
    # packs them in node-walk order: the same MULTISET of valid rows, each
    # read through what its ids stand for, and every other row padding
    for kind in ("filter_terms", "score_terms"):
        dterm, fterm = getattr(got, kind), getattr(fresh, kind)
        assert term_rows(dterm, drow) == term_rows(fterm, frow), kind
        assert_padding_rows(dterm, kind)


def term_rows(terms, pod_row) -> collections.Counter:
    """The valid rows of a term table as a multiset of (the selector's
    requirement content, namespaces, topology key, owner uid, weight):
    nothing of a row's place, its ``sel.index`` or its ``pod_idx``."""
    uid_of = {r: u for u, r in pod_row.items()}
    sel = jax.tree.map(np.asarray, terms.sel)
    uniq = [tuple(leaf[u].tobytes() for leaf in sel[:-1])
            for u in range(sel.sel_valid.shape[0])]
    ns_hot, topo_key, pod_idx, weight, valid = (
        np.asarray(getattr(terms, f))
        for f in ("ns_hot", "topo_key", "pod_idx", "weight", "valid"))
    return collections.Counter(
        (uniq[sel.index[i]], ns_hot[i].tobytes(), int(topo_key[i]),
         uid_of[int(pod_idx[i])], float(weight[i]))
        for i in np.nonzero(valid)[0])


def assert_padding_rows(terms, kind="") -> None:
    """Every row that is not valid holds what a build's padding row holds:
    zeros, and a selector that matches nothing."""
    pad = ~np.asarray(terms.valid)
    for f in ("ns_hot", "topo_key", "pod_idx", "weight"):
        assert not np.asarray(getattr(terms, f))[pad].any(), (kind, f)
    nil = np.asarray(terms.sel.index)[pad]
    assert not np.asarray(terms.sel.sel_valid)[nil].any(), kind
    assert not np.asarray(terms.sel.req_valid)[nil].any(), kind


def build_cache(n_nodes=6, pods_per_node=2, zones=3):
    cache = SchedulerCache()
    nodes = hollow.make_nodes(n_nodes, zones=zones)
    pods = []
    for i, n in enumerate(nodes):
        cache.add_node(n)
        for p in hollow.make_pods(pods_per_node, prefix=f"ex-{i}-",
                                  group_labels=3):
            p.spec.node_name = n.name
            cache.add_pod(p)
            pods.append(p)
    return cache, nodes, pods


# ---------------------------------------------------------------------------
# golden equivalence


def test_initial_resync_then_zero_delta():
    cache, _, _ = build_cache()
    dt = DeltaTensorizer()
    infos = snapshot_of(cache)
    c1, st1 = dt.refresh(infos)
    assert st1.resync and st1.reason == "initial"
    assert [n for n, _, _ in st1.spans] == ["resync"]
    assert_matches_fresh(dt, infos)
    # unchanged snapshot: the zero-delta chain case — same object, 0 rows
    c2, st2 = dt.refresh(snapshot_of(cache))
    assert c2 is c1
    assert st2.delta_rows == 0 and not st2.resync


def test_randomized_churn_stays_golden():
    """The acceptance golden: randomized commit/evict/update sequences,
    delta-applied tensors bit-match a rebuild after every cycle."""
    rng = random.Random(7)
    cache, nodes, pods = build_cache(n_nodes=8, pods_per_node=2, zones=4)
    live = list(pods)
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    seq = 0
    resyncs0 = dt.resync_count
    for step in range(40):
        op = rng.choice(["commit", "commit", "commit-term", "evict",
                         "update-node", "update-pod"])
        if op in ("commit", "commit-term"):
            seq += 1
            p = hollow.make_pod(f"new-{seq}")
            p.metadata.labels = {"app": f"group-{rng.randrange(3)}"}
            if op == "commit-term":
                # term-carrying pods ride the delta path too (term-only
                # rebuild, no resync)
                hollow.with_anti_affinity(p)
            p.spec.node_name = rng.choice(nodes).name
            cache.add_pod(p)
            live.append(p)
        elif op == "evict" and live:
            cache.remove_pod(live.pop(rng.randrange(len(live))))
        elif op == "update-node":
            old = rng.choice(nodes)
            new = copy.deepcopy(old)
            new.spec.unschedulable = not old.spec.unschedulable
            cache.update_node(old, new)
            nodes[nodes.index(old)] = new
        elif op == "update-pod" and live:
            i = rng.randrange(len(live))
            old = live[i]
            new = copy.copy(old)
            new.metadata = copy.deepcopy(old.metadata)
            new.metadata.labels["app"] = f"group-{rng.randrange(3)}"
            cache.update_pod(old, new)
            live[i] = new
        infos = snapshot_of(cache)
        _, st = dt.refresh(infos)
        assert_matches_fresh(dt, infos)
        if not st.resync:
            assert st.delta_rows > 0
        else:
            # vocab stays inside its caps by construction, so the only
            # legitimate fallback under this churn is pod-row exhaustion
            assert st.reason == "pod-axis-growth", st.reason
    del resyncs0


# ---------------------------------------------------------------------------
# the existing-term tables are KEPT while no dirty node's owners changed:
# the golden over every way an owner can change


COLORS = ("green", "blue", "red", "yellow")


def owner_pod(name, node="", kind="green", tier="a"):
    """A pod that owns existing-term rows, upstream's Mixed row's four
    templates by colour: ``green`` required hostname anti-affinity,
    ``blue`` required zone affinity, ``red`` preferred hostname affinity
    (weight 1), ``yellow`` preferred hostname anti-affinity (weight 1);
    each selects its own colour.  ``both``: a green pod that also carries
    blue's required affinity."""
    color = "green" if kind == "both" else kind
    p = hollow.make_pod(name)
    p.metadata.labels = {"color": color, "tier": tier}
    match = {"color": color}
    if kind in ("green", "both"):
        hollow.with_anti_affinity(p, match=match)
    if kind in ("blue", "both"):
        hollow.with_affinity(p, match=match)
    if kind in ("red", "yellow"):
        term = api.WeightedPodAffinityTerm(
            weight=1, pod_affinity_term=api.PodAffinityTerm(
                label_selector=api.LabelSelector(match_labels=match),
                topology_key=api.LABEL_HOSTNAME))
        p.spec.affinity = api.Affinity()
        if kind == "red":
            p.spec.affinity.pod_affinity = api.PodAffinity()
            p.spec.affinity.pod_affinity \
                .preferred_during_scheduling_ignored_during_execution \
                .append(term)
        else:
            p.spec.affinity.pod_anti_affinity = api.PodAntiAffinity()
            p.spec.affinity.pod_anti_affinity \
                .preferred_during_scheduling_ignored_during_execution \
                .append(term)
    p.spec.node_name = node
    return p


def plain_pod(name, node, tier="b"):
    p = hollow.make_pod(name)
    p.metadata.labels = {"tier": tier}
    p.spec.node_name = node
    return p


class OwnerWorld:
    """Upstream's Mixed row in small: EVERY node holds a term owner (its
    colour by node index) and a plain pod, the last node two owners; every
    label value the steps use is in the vocab from the first build, so no
    step crosses a cap."""

    def __init__(self, n_nodes=6, hard_pod_affinity_weight=1, mesh=None):
        self.cache = SchedulerCache()
        self.nodes = hollow.make_nodes(n_nodes, zones=3)
        self.owners, self.plains = [], []
        for i, n in enumerate(self.nodes):
            n.metadata.labels["rack"] = f"rack-{i % 2}"
            self.cache.add_node(n)
            self.add(owner_pod(f"own-{i}", n.name, COLORS[i % 4]))
            self.add(plain_pod(f"plain-{i}", n.name,
                               tier="ab"[i % 2]))
        self.add(owner_pod("own-second", self.nodes[-1].name, "red"))
        self.dt = DeltaTensorizer(
            hard_pod_affinity_weight=hard_pod_affinity_weight, mesh=mesh)
        self.seq = 0
        _, st = self.dt.refresh(snapshot_of(self.cache))
        assert st.resync

    def add(self, p):
        self.cache.add_pod(p)
        (self.owners if p.spec.affinity is not None
         else self.plains).append(p)
        return p

    def remove(self, p):
        self.cache.remove_pod(p)
        (self.owners if p.spec.affinity is not None
         else self.plains).remove(p)

    def move(self, p, node):
        """The same uid on another node (or taken off its node and put
        back: last in its pod order)."""
        self.remove(p)
        q = copy.copy(p)
        q.spec = copy.copy(p.spec)
        q.spec.node_name = node
        return self.add(q)

    def update(self, old, new):
        self.cache.update_pod(old, new)
        pods = self.owners if old.spec.affinity is not None else self.plains
        pods.remove(old)
        (self.owners if new.spec.affinity is not None
         else self.plains).append(new)
        return new

    def relabel_node(self, i):
        """The node takes the other rack: a label change inside the
        vocab (not the zone: the node tree orders by it), no pod churn."""
        old = self.nodes[i]
        new = copy.deepcopy(old)
        new.metadata.labels["rack"] = (
            "rack-1" if old.metadata.labels["rack"] == "rack-0"
            else "rack-0")
        new.spec.unschedulable = not old.spec.unschedulable
        self.cache.update_node(old, new)
        self.nodes[i] = new

    def fresh_name(self, prefix):
        self.seq += 1
        return f"{prefix}-{self.seq}"

    def refresh(self, expect=None):
        """One refresh, held to a from-scratch build() leaf for leaf; and
        to what the step expects of the term tables: ``kept`` (a dirty
        was written to tables that hold live rows, nothing of the term
        update ran), ``rebuilt`` (rows were written: an owner came or
        went), or ``untouched`` (the tables hold no live row)."""
        infos = snapshot_of(self.cache)
        _, st = self.dt.refresh(infos)
        assert_matches_fresh(self.dt, infos)
        names = [n for n, _, _ in st.spans]
        if "delta-build" in names:
            kept = st.span_args["delta-build"]["terms_kept"]
            rebuilt = "delta-terms" in names
            assert ("delta-terms-upload" in names) == (
                rebuilt and not st.resync)
            assert set(st.span_args) == (
                {"delta-build", "delta-terms"} if rebuilt
                else {"delta-build"})
            assert not (kept and rebuilt)
            if rebuilt:
                # a rebuild always has a changed owner to show for itself
                assert st.span_args["delta-terms"]["owners_changed"] >= 1
            got = "rebuilt" if rebuilt else "kept" if kept else "untouched"
        else:
            assert st.span_args == {}
            got = "resync" if st.resync else "noop"
        if expect is not None:
            assert got == expect, (got, expect, st.reason, names)
        return st, got


def _plain_arrives(w):
    w.add(plain_pod("late-plain", w.nodes[0].name))
    yield "kept"


def _plain_leaves(w):
    w.remove(w.plains[2])
    yield "kept"


def _owner_arrives_and_leaves(w):
    p = w.add(owner_pod("late-owner", w.nodes[1].name, "yellow"))
    yield "rebuilt"
    w.add(plain_pod("after", w.nodes[1].name))
    yield "kept"
    w.remove(p)
    yield "rebuilt"
    w.add(plain_pod("after-2", w.nodes[1].name))
    yield "kept"


def _owner_moves_up(w):
    w.move(w.owners[1], w.nodes[4].name)
    yield "rebuilt"
    w.add(plain_pod("after", w.nodes[4].name))
    w.add(plain_pod("after-2", w.nodes[1].name))
    yield "kept"


def _owner_moves_to_a_lower_node(w):
    w.move(w.owners[4], w.nodes[0].name)
    yield "rebuilt"
    w.add(plain_pod("after", w.nodes[0].name))
    yield "kept"


def _two_owners_swap_order(w):
    last = w.nodes[-1].name
    first = next(p for p in w.owners if p.spec.node_name == last)
    w.move(first, last)                # same node, same row: now walked last
    # until PR 47 the tables were a function of the ORDER the owners are
    # walked in and this rebuilt them; kept by row, nothing is written
    yield "kept"
    w.add(plain_pod("after", last))
    yield "kept"


def _owner_replaced_in_place_with_other_terms(w):
    old = w.owners[0]                                  # green
    new = owner_pod(old.metadata.name, old.spec.node_name, "yellow")
    new.metadata.uid = old.uid
    new.metadata.labels = dict(old.metadata.labels)
    assert new.uid == old.uid
    w.update(old, new)
    yield "rebuilt"
    w.add(plain_pod("after", old.spec.node_name))
    yield "kept"


def _owner_updated_in_place_with_equal_terms(w):
    """Re-parsed term lists that read the same compile the same rows."""
    old = w.owners[0]
    new = copy.deepcopy(old)
    new.metadata.labels["tier"] = "b"
    w.update(old, new)
    yield "kept"


def _owner_node_relabelled(w):
    w.relabel_node(2)
    yield "kept"


def _kept_cycle_grows_the_pod_axis(w):
    pp0 = w.dt.host.arrays["pod_node"].shape[0]
    free = pp0 - len(w.dt.pod_row)
    for k in range(free + 1):
        w.add(plain_pod(f"fill-{k}", w.nodes[k % len(w.nodes)].name))
    st, got = w.refresh()
    assert st.resync and st.reason == "pod-axis-growth" and got == "kept"
    assert [n for n, _, _ in st.spans] == ["delta-build", "resync"]
    assert w.dt.host.arrays["pod_node"].shape[0] > pp0
    w.add(plain_pod("after", w.nodes[0].name))
    yield "kept"


def _kept_straight_after_a_resync(w):
    w.cache.add_node(hollow.make_node("late-node", zone="zone-0"))
    st, _ = w.refresh("resync")
    assert st.reason == "node-set"
    w.add(plain_pod("after", w.nodes[0].name))
    yield "kept"
    w.dt.cycles_since_resync = w.dt.resync_interval
    w.add(plain_pod("after-2", w.nodes[1].name))
    st, _ = w.refresh("resync")
    assert st.reason == "anti-entropy"
    w.add(plain_pod("after-3", w.nodes[1].name))
    yield "kept"
    w.move(w.owners[0], w.nodes[3].name)
    yield "rebuilt"


def _plain_pod_on_a_node_without_owner(w):
    w.remove(w.owners[0])
    yield "rebuilt"
    w.add(plain_pod("after", w.nodes[0].name))
    # no owner on the dirty node; the tables' live rows (the other nodes'
    # owners') are kept all the same
    yield "kept"


OWNER_CHANGES = [
    _plain_arrives, _plain_leaves, _owner_arrives_and_leaves,
    _owner_moves_up, _owner_moves_to_a_lower_node, _two_owners_swap_order,
    _owner_replaced_in_place_with_other_terms,
    _owner_updated_in_place_with_equal_terms, _owner_node_relabelled,
    _kept_cycle_grows_the_pod_axis, _kept_straight_after_a_resync,
    _plain_pod_on_a_node_without_owner]


@pytest.mark.parametrize("steps", OWNER_CHANGES,
                         ids=[f.__name__.strip("_") for f in OWNER_CHANGES])
def test_term_tables_kept_or_rebuilt_stay_golden(steps):
    """Every way an owner can change, each refresh of the sequence held to
    a fresh build(): the only fault keeping can introduce is a table kept
    that should have been rebuilt, and this is what catches it."""
    w = OwnerWorld()
    for expect in steps(w):
        w.refresh(expect)


# ---------------------------------------------------------------------------
# a mirror row is refilled only when what it is filled FROM changed: the
# golden over every way a row's source can change beside rows that are kept


def refilled(st):
    """(node rows, pod rows) a delta build rewrote from their objects."""
    b = st.span_args["delta-build"]
    return b["node_rows_refilled"], b["pod_rows_refilled"]


def _set_again(w, i, edit):
    """Edit node i's Node object IN PLACE and hand the same object back,
    as an informer that reuses its objects does: no new identity to see,
    only ``NodeInfo.set_node`` having run."""
    n = w.nodes[i]
    edit(n)
    w.cache.update_node(n, n)
    w.add(plain_pod(w.fresh_name("arrives"), n.name))


def _node_relabelled_in_place_while_a_pod_arrives(w):
    _set_again(w, 2, lambda n: n.metadata.labels.__setitem__(
        "rack", "rack-1" if n.metadata.labels["rack"] == "rack-0"
        else "rack-0"))
    yield 1, 1


def _node_tainted_in_place_while_a_pod_arrives(w):
    _set_again(w, 1, lambda n: n.spec.taints.append(api.Taint(
        key="dedicated", value="batch",
        effect=api.TAINT_EFFECT_NO_SCHEDULE)))
    yield 1, 1
    _set_again(w, 1, lambda n: n.spec.taints.clear())
    yield 1, 1


def _node_cordoned_in_place_while_a_pod_arrives(w):
    _set_again(w, 3, lambda n: setattr(n.spec, "unschedulable", True))
    yield 1, 1
    # and beside it a node that is dirty for a pod's sake alone keeps
    # its static rows
    w.add(plain_pod("elsewhere", w.nodes[4].name))
    yield 0, 1


def _node_images_change(w):
    def pulls(*images):
        return lambda n: setattr(n.status, "images", [
            api.ContainerImage(names=[name], size_bytes=size)
            for name, size in images])
    _set_again(w, 0, pulls(("registry/a:1", 10 << 20),
                           ("registry/b:1", 20 << 20)))
    yield 1, 1
    _set_again(w, 5, pulls(("registry/a:1", 10 << 20)))
    yield 1, 1
    # node 0 drops b (no node carries it any more: its size reads 0 in a
    # fresh build) and pulls c
    _set_again(w, 0, pulls(("registry/a:1", 10 << 20),
                           ("registry/c:1", 30 << 20)))
    yield 1, 1
    w.add(plain_pod("elsewhere", w.nodes[0].name))
    yield 0, 1


def _resident_relabelled_beside_an_arrival(w):
    old = next(p for p in w.plains
               if p.spec.node_name == w.nodes[2].name)
    new = copy.deepcopy(old)
    new.metadata.labels["tier"] = (
        "a" if old.metadata.labels["tier"] == "b" else "b")
    w.update(old, new)
    w.add(plain_pod("arrives", w.nodes[2].name))
    yield 0, 2


def _resident_terminating_beside_an_arrival(w):
    old = next(p for p in w.plains
               if p.spec.node_name == w.nodes[3].name)
    new = copy.deepcopy(old)
    new.metadata.deletion_timestamp = 1234.5
    w.update(old, new)
    w.add(plain_pod("arrives", w.nodes[3].name))
    yield 0, 2
    back = copy.deepcopy(new)
    back.metadata.deletion_timestamp = None
    w.update(new, back)
    yield 0, 1


def _resident_untouched_over_twenty_cycles_of_churn(w):
    node = w.nodes[1].name
    residents = {p.uid for p in w.owners + w.plains
                 if p.spec.node_name == node}
    noted = {uid: w.dt.pod_src[uid] for uid in residents}
    last = None
    for k in range(20):
        if last is not None:
            w.remove(last)
        last = w.add(plain_pod(f"churn-{k}", node, tier="ab"[k % 2]))
        yield 0, 1
        assert all(w.dt.pod_src[uid] is noted[uid] for uid in residents)


ROW_SOURCES = [
    _node_relabelled_in_place_while_a_pod_arrives,
    _node_tainted_in_place_while_a_pod_arrives,
    _node_cordoned_in_place_while_a_pod_arrives, _node_images_change,
    _resident_relabelled_beside_an_arrival,
    _resident_terminating_beside_an_arrival,
    _resident_untouched_over_twenty_cycles_of_churn]
NODE_SOURCES, POD_SOURCES = ROW_SOURCES[:4], ROW_SOURCES[4:6]


def _ids(fs):
    return [f.__name__.strip("_") for f in fs]


def run_row_sources(steps):
    w = OwnerWorld()
    for expect in steps(w):
        st, _ = w.refresh()                # held to a fresh build()
        # (the pod axis may grow under the arrivals: no build() walk)
        assert st.reason in ("", "pod-axis-growth"), st.reason
        assert refilled(st) == expect


@pytest.mark.parametrize("steps", ROW_SOURCES, ids=_ids(ROW_SOURCES))
def test_a_mirror_row_is_refilled_when_its_source_changed(steps):
    """Each refresh held to a fresh build() AND to how many rows it
    rewrote: the rows whose Node / PodInfo changed, and no other."""
    run_row_sources(steps)


@pytest.mark.parametrize("steps", NODE_SOURCES, ids=_ids(NODE_SOURCES))
def test_the_golden_fails_when_the_node_marker_is_ignored(steps,
                                                          monkeypatch):
    """The mutation: a Node set again leaves ``node_generation`` where
    it was (what object identity alone would see of an in-place edit
    handed back with update_node(n, n)).  The golden must not pass."""
    from kubetpu.framework.types import NodeInfo
    orig = NodeInfo.set_node

    def set_node(self, node):
        first = self.node_generation
        orig(self, node)
        self.node_generation = first or self.node_generation
    monkeypatch.setattr(NodeInfo, "set_node", set_node)
    with pytest.raises(AssertionError):
        run_row_sources(steps)


@pytest.mark.parametrize("steps", POD_SOURCES, ids=_ids(POD_SOURCES))
def test_the_golden_fails_when_the_pod_marker_cannot_see_the_change(
        steps, monkeypatch):
    """The mutation: a pod updated under its uid keeps its PodInfo
    OBJECT, rewritten in place (which no writer does: update_pod is a
    remove and an add, and add_pod makes a new one).  The marker is that
    object, so the row is kept and the golden must not pass."""
    from kubetpu.framework.types import NodeInfo, PodInfo
    orig, seen = NodeInfo.add_pod, {}

    def add_pod(self, pod, pinfo=None):
        fresh = PodInfo(pod)
        pi = seen.setdefault(pod.uid, fresh)
        for slot in PodInfo.__slots__:
            setattr(pi, slot, getattr(fresh, slot))
        orig(self, pod, pi)
    monkeypatch.setattr(NodeInfo, "add_pod", add_pod)
    with pytest.raises(AssertionError):
        run_row_sources(steps)


def _scrambled(w):
    """Rows out of walk order and a freed row, so that a rebuild MOVES
    rows (it packs them in node-walk order), those of a node's two
    owners among them."""
    w.add(owner_pod("scramble-owner", w.nodes[2].name, "red"))
    w.refresh("rebuilt")
    w.remove(next(p for p in w.plains
                  if p.spec.node_name == w.nodes[0].name))
    w.add(plain_pod("scramble-a", w.nodes[4].name))
    w.add(plain_pod("scramble-b", w.nodes[2].name))
    w.remove(next(p for p in w.plains
                  if p.spec.node_name == w.nodes[1].name))
    w.refresh("kept")


def _by_vocab_growth(w):
    kv_cap, k = w.dt.builder.table.kv.cap, 0
    while w.dt.builder.table.kv.cap == kv_cap:
        k += 1
        p = plain_pod(f"grow-{k}", w.nodes[k % len(w.nodes)].name)
        p.metadata.labels["uniq"] = f"v{k}"
        w.add(p)
        st, _ = w.refresh()
    return st


def _by_anti_entropy(w):
    w.dt.cycles_since_resync = w.dt.resync_interval
    w.add(plain_pod("tick", w.nodes[3].name))
    return w.refresh()[0]


def _by_node_set(w):
    n = hollow.make_node("late-node", zone="zone-0")
    n.metadata.labels["rack"] = "rack-0"
    w.cache.add_node(n)
    return w.refresh()[0]


def _by_delta_too_large(w):
    w.dt.max_delta_frac = 0.0
    w.add(plain_pod("one-too-many", w.nodes[3].name))
    st = w.refresh()[0]
    w.dt.max_delta_frac = 1.0
    return st


def _by_label_capacity(w):
    p = plain_pod("many-labels", w.nodes[3].name)
    width = w.dt.host.arrays["_pod_kv_ids"].shape[1]
    for k in range(width):
        p.metadata.labels[f"extra-{k}"] = "x"
    w.add(p)
    return w.refresh()[0]


def _by_pod_axis_growth(w):
    free = w.dt.host.arrays["pod_node"].shape[0] - len(w.dt.pod_row)
    for k in range(free + 1):
        w.add(plain_pod(f"fill-{k}", w.nodes[k % len(w.nodes)].name))
    return w.refresh()[0]


RESYNCS = {"vocab-growth": _by_vocab_growth,
           "anti-entropy": _by_anti_entropy, "node-set": _by_node_set,
           "delta-too-large": _by_delta_too_large,
           "label-capacity": _by_label_capacity,
           "pod-axis-growth": _by_pod_axis_growth}


def assert_markers_noted(dt, infos):
    """Every marker names what the mirror row was filled from, as the
    resident state stands NOW: the PodInfo on the node, the node's row,
    the owner's delta row; the Node's ``node_generation``."""
    assert set(dt.pod_src) == set(dt.pod_row)
    for i, ni in enumerate(infos):
        assert dt.node_src[ni.node_name] == ni.node_generation
        for pi in ni.pods:
            src, i_was, owner = dt.pod_src[pi.pod.uid]
            assert src is pi and i_was == i
            assert owner is None or owner.row == dt.pod_row[pi.pod.uid]


def run_resync_then_churn(reason, noted=assert_markers_noted):
    w = OwnerWorld()
    _scrambled(w)
    st = RESYNCS[reason](w)
    assert st.resync and st.reason == reason
    noted(w.dt, snapshot_of(w.cache))
    # what the markers guard, straight after: a resident replaced in
    # place, an owner whose terms change on the node that holds a second
    # owner (the node's owners are re-read: the one that stayed from its
    # marker, with the row noted there), a Node set again
    _set_again(w, 2, lambda n: setattr(n.spec, "unschedulable", True))
    old = next(p for p in w.owners if p.metadata.name == "own-2")
    new = owner_pod(old.metadata.name, old.spec.node_name, "yellow")
    new.metadata.uid = old.uid
    w.update(old, new)
    w.refresh("rebuilt")
    noted(w.dt, snapshot_of(w.cache))
    w.add(plain_pod("after", w.nodes[1].name))
    w.refresh("kept")


@pytest.mark.parametrize("reason", list(RESYNCS))
def test_no_marker_survives_a_resync(reason):
    run_resync_then_churn(reason)


@pytest.mark.parametrize("reason", [r for r in RESYNCS
                                    if r != "pod-axis-growth"])
def test_the_golden_fails_when_a_resync_keeps_the_markers(reason,
                                                          monkeypatch):
    """The mutation: _resync() leaves ``pod_src`` / ``node_src`` and the
    term tables' rows by owner (``term_tables``) as they were.  A rebuild
    packs the term rows anew in a new mirror, so an owner that goes would
    tombstone the rows it had BEFORE, in the mirror of before.  (The two
    markers alone fail nothing since PR 47: a stale ``pod_src`` makes an
    owner replaced in place an owner gone and an owner come, which writes
    the rows it would have kept.  pod-axis growth does not pass through
    _resync: it moves no row and no id, and the markers the same refresh
    noted stand.)"""
    orig = DeltaTensorizer._resync

    def keeps(self, *a, **kw):
        pod_src, node_src = self.pod_src, self.node_src
        term_tables = self.term_tables
        out = orig(self, *a, **kw)
        if pod_src:                       # not the initial build
            self.pod_src, self.node_src = pod_src, node_src
            self.term_tables = term_tables
        return out
    monkeypatch.setattr(DeltaTensorizer, "_resync", keeps)
    with pytest.raises(AssertionError):
        # the golden itself, not the look at the markers
        run_resync_then_churn(reason, noted=lambda dt, infos: None)


@pytest.mark.parametrize("hw", [1, 0])
def test_required_affinity_owns_a_row_only_at_a_hard_weight(hw):
    """``hard_pod_affinity_weight`` 0: required affinity compiles to no
    score row, so a pod with nothing else is no owner (the dirty-node
    check and the rebuild's walk ask ``pod_has_terms(pi, hw)`` alike),
    while a pod that also carries anti-affinity still is one."""
    w = OwnerWorld(hard_pod_affinity_weight=hw)
    node = w.nodes[2].name                       # its own owner: red
    blue = w.add(owner_pod("late-blue", node, "blue"))
    st, _ = w.refresh("rebuilt" if hw else "kept")
    rows = int(np.asarray(w.dt.cluster.score_terms.valid).sum())
    assert rows == (6 if hw else 3)      # blue x 3, red x 2, yellow
    w.add(plain_pod("after", node))
    w.refresh("kept")
    w.remove(blue)
    w.refresh("rebuilt" if hw else "kept")
    w.add(owner_pod("late-both", node, "both"))
    w.refresh("rebuilt")
    w.add(plain_pod("after-2", node))
    w.refresh("kept")


@pytest.mark.parametrize("hw", [1, 0])
def test_randomized_owner_churn_stays_golden(hw):
    """200 steps mixing every case above on a world of owners: plain and
    owner arrivals and departures, moves, reorders, in-place replacements
    with other and with equal terms, node relabels, pod-axis growth and
    forced anti-entropy resyncs — the resident tables match a rebuild
    after EVERY refresh, kept or rebuilt."""
    rng = random.Random(30 + hw)
    w = OwnerWorld(n_nodes=8, hard_pod_affinity_weight=hw)
    kinds = COLORS + ("both",)
    seen = {"kept": 0, "rebuilt": 0, "untouched": 0, "resync": 0,
            "noop": 0}
    for step in range(200 if hw else 60):
        for _ in range(rng.choice([1, 1, 2, 3])):
            op = rng.choice(["plain", "plain", "plain-leaves",
                             "plain-leaves", "owner", "owner-leaves",
                             "move", "reorder", "replace", "update",
                             "relabel", "anti-entropy"])
            node = rng.choice(w.nodes).name
            if op == "plain":
                w.add(plain_pod(w.fresh_name("p"), node,
                                tier=rng.choice("ab")))
            elif op == "plain-leaves" and w.plains:
                w.remove(rng.choice(w.plains))
            elif op == "owner":
                w.add(owner_pod(w.fresh_name("o"), node, rng.choice(kinds),
                                tier=rng.choice("ab")))
            elif op == "owner-leaves" and w.owners:
                w.remove(rng.choice(w.owners))
            elif op == "move" and w.owners:
                w.move(rng.choice(w.owners), node)
            elif op == "reorder" and w.owners:
                p = rng.choice(w.owners)
                w.move(p, p.spec.node_name)
            elif op == "replace" and w.owners:
                old = rng.choice(w.owners)
                new = owner_pod(old.metadata.name, old.spec.node_name,
                                rng.choice(kinds))
                new.metadata.uid = old.uid
                w.update(old, new)
            elif op == "update" and (w.owners or w.plains):
                old = rng.choice(w.owners + w.plains)
                new = copy.deepcopy(old)
                new.metadata.labels["tier"] = rng.choice("ab")
                w.update(old, new)
            elif op == "relabel":
                w.relabel_node(rng.randrange(len(w.nodes)))
            elif op == "anti-entropy" and rng.random() < 0.3:
                w.dt.cycles_since_resync = w.dt.resync_interval
        st, got = w.refresh()
        seen[got] += 1
        if st.resync:
            assert st.reason in ("pod-axis-growth", "anti-entropy"), \
                st.reason
    assert seen["kept"] >= 10 and seen["rebuilt"] >= 10, seen


# ---------------------------------------------------------------------------
# the term tables are kept by ROW (PR 47): what a row's life looks like from
# the tensorizer's own books, each refresh still held to a fresh build()


def _said(st):
    return st.span_args["delta-terms"]


def _rows_of(w, p, field="filter_terms"):
    return w.dt.term_tables[field].owner_rows.get(p.uid, ())


def _a_tombstoned_row_is_reused_by_the_next_arrival(w):
    gone = w.owners[0]                                   # green: one row
    row, = _rows_of(w, gone)
    w.remove(gone)
    st, _ = w.refresh("rebuilt")
    assert (_said(st)["rows_written"], _said(st)["rows_free"]) == (1, 1)
    assert w.dt.term_tables["filter_terms"].free == [row]
    assert not np.asarray(w.dt.cluster.filter_terms.valid)[row]
    late = w.add(owner_pod("late-green", w.nodes[3].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert _rows_of(w, late) == (row,)
    assert (_said(st)["rows_written"], _said(st)["rows_free"]) == (1, 0)
    # gone and come in ONE refresh: the row is tombstoned and taken again
    w.remove(late)
    again = w.add(owner_pod("again-green", w.nodes[1].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert _rows_of(w, again) == (row,)
    assert (_said(st)["rows_written"], _said(st)["owners_changed"]) == (2, 2)


def _an_owner_with_several_terms(w):
    p = owner_pod("many", w.nodes[2].name, "both")       # filter + score
    hollow.with_anti_affinity(p, topo_key=api.LABEL_ZONE,
                              match={"color": "red"})
    hollow.with_anti_affinity(p, match={"tier": "a"})
    w.add(p)
    f0 = w.dt.term_tables["filter_terms"].live
    s0 = w.dt.term_tables["score_terms"].live
    st, _ = w.refresh("rebuilt")
    assert len(_rows_of(w, p)) == 3
    assert len(_rows_of(w, p, "score_terms")) == 1
    assert (_said(st)["filter_rows"], _said(st)["score_rows"],
            _said(st)["rows_written"], _said(st)["owners_changed"]) \
        == (f0 + 3, s0 + 1, 4, 1)
    w.remove(p)
    st, _ = w.refresh("rebuilt")
    assert (_said(st)["filter_rows"], _said(st)["score_rows"],
            _said(st)["rows_written"], _said(st)["rows_free"]) \
        == (f0, s0, 4, 4)
    assert not _rows_of(w, p) and not _rows_of(w, p, "score_terms")


def _a_new_unique_selector(w):
    tt = w.dt.term_tables["filter_terms"]
    # the build's two green rows fill their bucket: the first arrival
    # grows it, and with it comes the nil selector that padding rows name
    w.add(owner_pod("grows-Et", w.nodes[0].name, "green"))
    w.refresh("rebuilt")
    u0 = len(tt.sel_keys)
    U0 = tt.terms.sel.sel_valid.shape[0]
    seen = w.add(owner_pod("seen", w.nodes[0].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert _said(st)["wholesale"] == 0 and len(tt.sel_keys) == u0
    added = 0
    while tt.terms.sel.sel_valid.shape[0] == U0:         # until U grows
        p = owner_pod(w.fresh_name("new-sel"), w.nodes[1].name, "green")
        # a selector over values the vocab holds: no cap is crossed
        hollow.with_anti_affinity(p, match={
            "color": COLORS[added % 4], "tier": "ab"[added // 4]})
        w.add(p)
        added += 1
        st, _ = w.refresh("rebuilt")
        assert not st.resync, st.reason
        # a selector the table has not seen crosses whole, inside U too
        assert _said(st)["wholesale"] == 1
        assert len(tt.sel_keys) == u0 + added
    assert tt.terms.sel.sel_valid.shape[0] == 2 * U0
    assert w.dt.cluster.filter_terms.sel.sel_valid.shape[0] == 2 * U0
    w.remove(seen)
    st, _ = w.refresh("rebuilt")
    assert _said(st)["wholesale"] == 0


def _Et_crosses_its_bucket(w):
    tt = w.dt.term_tables["filter_terms"]
    assert tt.high == tt.terms.valid.shape[0] == 2      # the build's: full
    w.add(owner_pod("grows-Et", w.nodes[0].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert (_said(st)["Et"], _said(st)["wholesale"]) == (4, 1)
    Et0 = 4
    for k in range(Et0 - tt.high):
        w.add(owner_pod(f"fill-{k}", w.nodes[k % 6].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert (_said(st)["Et"], _said(st)["wholesale"]) == (Et0, 0)
    assert tt.high == tt.live == Et0
    w.add(owner_pod("one-more", w.nodes[0].name, "green"))
    st, _ = w.refresh("rebuilt")
    assert not st.resync
    assert (_said(st)["Et"], _said(st)["wholesale"],
            _said(st)["filter_rows"]) == (2 * Et0, 1, Et0 + 1)
    assert w.dt.cluster.filter_terms.valid.shape[0] == 2 * Et0
    # never shrinking: the owners go, the bucket stays until a resync
    for p in [p for p in w.owners if p.metadata.name.startswith("fill-")]:
        w.remove(p)
    st, _ = w.refresh("rebuilt")
    assert (_said(st)["Et"], _said(st)["wholesale"]) == (2 * Et0, 0)
    assert _said(st)["rows_free"] == tt.high - tt.live == 1


def _a_resync_after_tombstones_packs_again(w):
    for p in [w.owners[0], w.owners[4]]:                 # green, green
        w.remove(p)
    p = owner_pod("dead-selector", w.nodes[1].name, "green")
    hollow.with_anti_affinity(p, match={"color": "red", "tier": "b"})
    w.add(p)
    w.refresh("rebuilt")
    w.remove(p)
    st, _ = w.refresh("rebuilt")
    tt = w.dt.term_tables["filter_terms"]
    assert tt.free and tt.high > tt.live
    dead = len(tt.sel_keys)
    w.dt.cycles_since_resync = w.dt.resync_interval
    w.add(plain_pod("tick", w.nodes[3].name))
    st, _ = w.refresh("resync")
    assert st.reason == "anti-entropy"
    for field, tt in w.dt.term_tables.items():
        valid = np.asarray(getattr(w.dt.cluster, field).valid)
        assert tt.free == [] and tt.high == tt.live == int(valid.sum())
        assert valid[:tt.live].all()                     # packed again
        assert sorted(r for rows in tt.owner_rows.values() for r in rows) \
            == list(range(tt.live))
    # and the selector no owner names any more is gone with them
    assert len(w.dt.term_tables["filter_terms"].sel_keys) < dead
    late = w.add(owner_pod("late", w.nodes[0].name, "green"))
    w.refresh("rebuilt")
    assert _rows_of(w, late) == (w.dt.term_tables["filter_terms"].live - 1,)


def _a_placement_equal_on_the_kept_and_the_fresh_tables(w):
    from kubetpu.framework.types import PodInfo
    from kubetpu.models import programs
    from kubetpu.models.batch import PodBatchBuilder
    # scramble the rows: owners of every colour go and come
    for p in [w.owners[0], w.owners[1], w.owners[3]]:
        w.remove(p)
    w.refresh("rebuilt")
    for k, kind in enumerate(("yellow", "blue", "green", "red", "both")):
        w.add(owner_pod(f"mix-{k}", w.nodes[(2 * k) % 6].name, kind))
    w.remove(w.owners[0])
    w.refresh("rebuilt")
    pending = []
    for k, color in enumerate(COLORS + COLORS):
        p = hollow.make_pod(f"pending-{k}")
        p.metadata.labels = {"color": color, "tier": "ab"[k % 2]}
        pending.append(PodInfo(p))
    infos = snapshot_of(w.cache)
    _, st = w.dt.refresh(infos, pending=pending)
    assert not st.resync
    fresh = SnapshotBuilder(
        table=copy.deepcopy(w.dt.builder.table)).build(infos).to_device()
    batch = jax.tree.map(np.asarray,
                         PodBatchBuilder(w.dt.builder.table).build(pending))
    cfg = programs.ProgramConfig(
        filters=("InterPodAffinity",), scores=(("InterPodAffinity", 1),),
        hostname_topokey=max(
            w.dt.builder.table.topokey.get(api.LABEL_HOSTNAME), 0))
    kept = programs.filter_and_score(w.dt.cluster, batch, cfg)
    built = programs.filter_and_score(fresh, batch, cfg)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(built)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the tables decide something: a node is refused, a score differs
    feasible = np.asarray(kept.feasible)[:len(pending), :len(w.nodes)]
    assert feasible.any() and not feasible.all()
    assert len(np.unique(np.asarray(kept.scores)[:len(pending)])) > 1


TERM_ROWS = [_a_tombstoned_row_is_reused_by_the_next_arrival,
             _an_owner_with_several_terms, _a_new_unique_selector,
             _Et_crosses_its_bucket, _a_resync_after_tombstones_packs_again,
             _a_placement_equal_on_the_kept_and_the_fresh_tables]


@pytest.mark.parametrize("case", TERM_ROWS, ids=_ids(TERM_ROWS))
def test_the_term_tables_are_kept_by_row(case):
    case(OwnerWorld())


@pytest.mark.parametrize("case", TERM_ROWS[:5], ids=_ids(TERM_ROWS[:5]))
def test_the_term_tables_are_kept_by_row_on_a_mesh(case):
    """The same lives of a row with the resident cluster sharded over a
    2 x 4 mesh: the tables ride replicated, the written rows are
    replicated to them and scattered by the same program, a table that
    crosses whole is replicated again; the verifier's per-leaf sums of
    device and mirror agree after the last refresh."""
    from kubetpu.parallel import mesh as pmesh
    w = OwnerWorld(n_nodes=8, mesh=pmesh.make_mesh((2, 4)))
    case(w)
    for leaf in jax.tree.leaves((w.dt.cluster.filter_terms,
                                 w.dt.cluster.score_terms)):
        assert leaf.sharding.is_fully_replicated
    assert w.dt.verify()


# ---------------------------------------------------------------------------
# fallback triggers


def test_intern_growth_falls_back_to_resync():
    cache, nodes, _ = build_cache()
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    kv_cap = dt.builder.table.kv.cap
    seq = 0
    # churn distinct label VALUES until the kv pow2 bucket doubles
    while dt.builder.table.kv.cap == kv_cap:
        seq += 1
        p = hollow.make_pod(f"grow-{seq}")
        p.metadata.labels = {"uniq": f"v{seq}"}
        p.spec.node_name = nodes[seq % len(nodes)].name
        cache.add_pod(p)
        infos = snapshot_of(cache)
        _, st = dt.refresh(infos)
        assert_matches_fresh(dt, infos)
    assert st.resync and st.reason == "vocab-growth"


def test_term_pod_churn_is_delta_served_with_term_refresh():
    """Term-carrying pod churn no longer forces a full resync: the
    ExistingTerms rebuild from the term OWNERS alone (delta-terms span)
    and the rest of the cycle stays on the scatter path — bit-exact
    against a rebuild both after the add and after the evict."""
    cache, nodes, _ = build_cache()
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    resyncs0 = dt.resync_count
    p = hollow.make_pod("affinity-pod")
    hollow.with_anti_affinity(p)
    p.spec.node_name = nodes[0].name
    cache.add_pod(p)
    infos = snapshot_of(cache)
    _, st = dt.refresh(infos)
    assert not st.resync, st.reason
    assert "delta-terms" in [n for n, _, _ in st.spans]
    assert_matches_fresh(dt, infos)
    # REMOVING the term pod drops its term rows, still without a resync
    cache.remove_pod(p)
    infos = snapshot_of(cache)
    _, st = dt.refresh(infos)
    assert not st.resync, st.reason
    assert "delta-terms" in [n for n, _, _ in st.spans]
    assert_matches_fresh(dt, infos)
    assert dt.resync_count == resyncs0


def test_pending_vocab_growth_resyncs_even_with_zero_node_churn():
    """Review regression: pending/nominated pods intern BEFORE the dirty
    scan, so a cycle with zero node churn whose pending pod carries a
    never-seen topology key must still resync — serving the resident
    tensors would leave the new topo_pair column all -1 (every node
    silently 'lacks' the key)."""
    from kubetpu.framework.types import PodInfo
    cache, _, _ = build_cache()
    dt = DeltaTensorizer()
    infos = snapshot_of(cache)
    dt.refresh(infos)
    p = hollow.make_pod("pending-new-key")
    hollow.with_spread(p, "custom.io/rack")
    _, st = dt.refresh(infos, pending=[PodInfo(p)])
    assert st.resync and st.reason == "vocab-growth"
    assert_matches_fresh(dt, infos)
    # same pending pod next cycle: strings already in the (fresh) table
    _, st = dt.refresh(infos, pending=[PodInfo(p)])
    assert not st.resync and st.delta_rows == 0


def test_resync_compacts_dead_vocab():
    """A full resync restarts the intern table: label values of departed
    pods (pod-template-hash churn) stop occupying vocab — and so resident
    tensor width — forever."""
    cache, nodes, _ = build_cache()
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    base_len = len(dt.builder.table.kv)
    doomed = []
    for i in range(40):
        p = hollow.make_pod(f"churn-{i}")
        p.metadata.labels = {"rollout-hash": f"h{i:04d}"}
        p.spec.node_name = nodes[i % len(nodes)].name
        cache.add_pod(p)
        doomed.append(p)
    infos = snapshot_of(cache)
    dt.refresh(infos)
    grown_len = len(dt.builder.table.kv)
    assert grown_len >= base_len + 40
    for p in doomed:
        cache.remove_pod(p)
    infos = snapshot_of(cache)
    dt.refresh(infos)
    # force the anti-entropy resync: the compaction point
    dt.cycles_since_resync = dt.resync_interval
    _, st = dt.refresh(infos)
    assert st.resync and st.reason == "anti-entropy"
    assert len(dt.builder.table.kv) < grown_len - 30
    assert_matches_fresh(dt, infos)


def test_pod_moving_to_lower_indexed_node_keeps_its_row_mapping():
    """Review regression: a same-uid pod moving from a higher- to a
    lower-indexed node between refreshes must be freed across ALL dirty
    nodes before the add scan — the interleaved single-pass version saw
    the stale mapping on the destination node, skipped the add, then
    popped the row and crashed the refill with a KeyError."""
    cache, nodes, pods = build_cache()
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    mover = pods[-1]                      # lives on the LAST node
    cache.remove_pod(mover)
    moved = copy.copy(mover)
    moved.spec = copy.copy(mover.spec)
    moved.spec.node_name = nodes[0].name  # re-added on the FIRST node
    cache.add_pod(moved)
    infos = snapshot_of(cache)
    _, st = dt.refresh(infos)
    assert not st.resync, st.reason
    assert_matches_fresh(dt, infos)


def test_node_set_change_falls_back_to_resync():
    cache, nodes, _ = build_cache()
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    cache.add_node(hollow.make_node("late-node", zone="zone-0"))
    infos = snapshot_of(cache)
    _, st = dt.refresh(infos)
    assert st.resync and st.reason == "node-set"
    assert_matches_fresh(dt, infos)


def test_pod_axis_growth_reuploads_without_build(monkeypatch):
    """Pod-row exhaustion pads the mirror to the next pow2 bucket and
    re-uploads — WITHOUT re-running the build() walk."""
    from kubetpu.state import tensors as tensors_mod
    cache, nodes, _ = build_cache(n_nodes=4, pods_per_node=2, zones=2)
    dt = DeltaTensorizer()
    dt.refresh(snapshot_of(cache))
    pp0 = dt.host.arrays["pod_node"].shape[0]
    builds = [0]
    orig = tensors_mod.SnapshotBuilder.build

    def counted(self, *a, **kw):
        builds[0] += 1
        return orig(self, *a, **kw)
    monkeypatch.setattr(tensors_mod.SnapshotBuilder, "build", counted)
    seq = 0
    while dt.host.arrays["pod_node"].shape[0] == pp0:
        seq += 1
        p = hollow.make_pod(f"fill-{seq}")
        p.metadata.labels = {"app": "group-0"}
        p.spec.node_name = nodes[seq % len(nodes)].name
        cache.add_pod(p)
        infos = snapshot_of(cache)
        before = builds[0]        # assert_matches_fresh builds on purpose;
        _, st = dt.refresh(infos)  # the REFRESH itself must not
        assert builds[0] == before, "pod-axis growth re-walked the world"
        assert_matches_fresh(dt, infos)
    assert st.resync and st.reason == "pod-axis-growth"


def test_anti_entropy_resync_interval():
    cache, nodes, _ = build_cache()
    dt = DeltaTensorizer(resync_interval=3)
    dt.refresh(snapshot_of(cache))
    reasons = []
    for seq in range(5):
        p = hollow.make_pod(f"tick-{seq}")
        p.metadata.labels = {"app": "group-0"}
        p.spec.node_name = nodes[0].name
        cache.add_pod(p)
        _, st = dt.refresh(snapshot_of(cache))
        reasons.append(st.reason)
    assert "anti-entropy" in reasons


# ---------------------------------------------------------------------------
# a refresh costs what CHANGED (PR 44): at thirty pods a node it visits the
# arrivals, sends the rows it refilled or cleared and no other, and (PR 47)
# walks no node's pods, an owner's coming or going included


class DenseWorld:
    """Eight nodes of thirty plain pods (the ``sigscale-150k`` density),
    one refresh behind it; ``refresh()`` holds the one it makes to a fresh
    build() and to what it may visit and send."""

    PER_NODE = 30

    def __init__(self, monkeypatch):
        from kubetpu.state import delta as delta_mod
        self.cache, self.nodes, pods = build_cache(
            n_nodes=8, pods_per_node=self.PER_NODE)
        self.on = {n.name: [] for n in self.nodes}
        for p in pods:
            self.on[p.spec.node_name].append(p)
        self.dt = DeltaTensorizer()
        self.seq = 0
        self.cleared, self.filled, self.sent = [], [], []
        for name, into in (("clear_pod_row", self.cleared),
                           ("fill_pod_row", self.filled)):
            monkeypatch.setattr(delta_mod, name, self._spy(
                getattr(delta_mod, name), into))
        gather = delta_mod.gather_delta

        def gathered(host, node_rows, pod_rows, **kw):
            self.sent.append(list(pod_rows))
            return gather(host, node_rows, pod_rows, **kw)
        monkeypatch.setattr(delta_mod, "gather_delta", gathered)
        assert self.refresh().reason == "initial"

    @staticmethod
    def _spy(fn, into):
        def spied(d, row, *a):
            into.append(row)
            return fn(d, row, *a)
        return spied

    def arrive(self, i, pod=None):
        self.seq += 1
        p = pod or plain_pod(f"new-{self.seq}", self.nodes[i].name)
        p.spec.node_name = self.nodes[i].name
        self.cache.add_pod(p)
        self.on[p.spec.node_name].append(p)
        return p

    def leave(self, i, k=0):
        p = self.on[self.nodes[i].name].pop(k)
        self.cache.remove_pod(p)
        return p

    def refresh(self):
        del self.cleared[:], self.filled[:], self.sent[:]
        infos = snapshot_of(self.cache)
        dirty = [ni for ni in infos
                 if ni.generation != self.dt.node_gen.get(ni.node_name)]
        _, st = self.dt.refresh(infos)
        assert_matches_fresh(self.dt, infos)
        uids = self.dt.pod_uid_list()
        assert len(uids) == self.dt.host.arrays["pod_node"].shape[0]
        assert {u: r for r, u in enumerate(uids) if u} == self.dt.pod_row
        if "delta-build" not in st.span_args:
            return st
        said = st.span_args["delta-build"]
        rows = set(self.cleared) | set(self.filled)
        assert said["pod_rows_refilled"] == len(self.filled) \
            == len(set(self.filled))
        assert said["pod_rows_seen"] == len(rows)
        assert st.delta_rows == len(dirty) + len(rows)
        if not st.resync:       # growth re-uploads: nothing is gathered
            assert self.sent == [sorted(rows)]
        assert said["pods_walked"] == len(self.filled)
        return st


def _one_in_one_out(w):
    """The benchmark client's churn: on k nodes a pod arrives and the one
    that arrived a refresh earlier leaves."""
    last = []
    for cycle in range(4):
        for i, p in last:
            w.on[w.nodes[i].name].remove(p)
            w.cache.remove_pod(p)
        last = [(i, w.arrive(i))
                for i in range(cycle % 3, 8, 2 + cycle % 2)]
        st = yield
        assert st.span_args["delta-build"]["pods_walked"] == len(last)


def _replaced_on_its_node_under_its_uid(w):
    old = w.on[w.nodes[2].name][7]
    new = copy.copy(old)
    new.metadata = copy.copy(old.metadata)
    new.metadata.labels = {"app": "group-1", "tier": "b"}
    w.cache.update_pod(old, new)
    w.on[w.nodes[2].name][7] = new
    row = w.dt.pod_row[old.uid]
    st = yield
    # the row is refilled where it is; nothing is freed
    assert w.dt.pod_row[new.uid] == row
    assert (w.cleared, w.filled) == ([], [row]) and not w.dt.free_rows
    assert st.span_args["delta-build"]["pods_walked"] == 1


def _moved_between_two_dirty_nodes(w):
    for src, dst in ((6, 1), (1, 5)):       # to a lower node, to a higher
        p = w.leave(src, 3)
        moved = copy.copy(p)
        moved.spec = copy.copy(p.spec)
        w.arrive(dst, moved)
        w.arrive(src)                       # beside a plain arrival
        st = yield
        assert st.span_args["delta-build"]["pods_walked"] == 2
        assert len(w.cleared) == 1 and len(w.filled) == 2


def _an_owner_arrives_where_none_was_and_leaves(w):
    owner = w.arrive(4, owner_pod("late-owner", kind="green"))
    st = yield
    assert "delta-terms" in st.span_args
    # the owner alone: until PR 47 its node's thirty were read as well,
    # for the node's ordered owners
    assert st.span_args["delta-build"]["pods_walked"] == 1
    assert st.span_args["delta-terms"]["rows_written"] == 1
    w.arrive(4)
    w.arrive(5)
    st = yield
    # a plain pod beside the owner: the tables are kept, nothing is walked
    said = st.span_args["delta-build"]
    assert (said["terms_kept"], said["pods_walked"]) == (1, 2)
    w.on[w.nodes[4].name].remove(owner)
    w.cache.remove_pod(owner)
    w.arrive(5)
    st = yield
    assert st.span_args["delta-terms"]["owners_changed"] == 1
    assert st.span_args["delta-build"]["pods_walked"] == 1
    assert all(owner.uid not in tt.owner_rows and not tt.live
               for tt in w.dt.term_tables.values())
    w.arrive(4)
    st = yield
    said = st.span_args["delta-build"]
    assert (said["terms_kept"], said["pods_walked"]) == (0, 1)


def _the_pod_axis_grows(w):
    pp0 = w.dt.host.arrays["pod_node"].shape[0]
    w.leave(0)
    for k in range(pp0 - len(w.dt.pod_row) + 2):
        w.arrive(k % 8)
    st = yield
    assert st.reason == "pod-axis-growth"
    assert w.dt.host.arrays["pod_node"].shape[0] == 2 * pp0
    w.leave(3)
    w.arrive(6)
    st = yield
    assert not st.resync and len(w.filled) == 1


def _a_resync_in_the_middle(w):
    w.leave(1)
    w.arrive(2)
    yield
    w.dt.cycles_since_resync = w.dt.resync_interval
    w.leave(2, 5)
    w.arrive(3)
    st = yield
    assert st.reason == "anti-entropy"
    w.leave(3, 9)
    w.arrive(1)
    w.arrive(1)
    st = yield
    assert not st.resync
    assert st.span_args["delta-build"]["pods_walked"] == 2


CHANGES = [_one_in_one_out, _replaced_on_its_node_under_its_uid,
           _moved_between_two_dirty_nodes,
           _an_owner_arrives_where_none_was_and_leaves,
           _the_pod_axis_grows, _a_resync_in_the_middle]


@pytest.mark.parametrize("steps", CHANGES, ids=_ids(CHANGES))
def test_a_refresh_visits_and_sends_what_changed(steps, monkeypatch):
    """After EVERY refresh the resident tensors are a fresh build()'s,
    ``pod_rows_seen`` is the rows refilled or cleared (and they are what
    ``gather_delta`` was given), and ``pods_walked`` is the arrivals:
    never the thirty of a node, with or without an owner among them."""
    w = DenseWorld(monkeypatch)
    gen = steps(w)
    next(gen)
    while True:
        st = w.refresh()
        try:
            gen.send(st)
        except StopIteration:
            break


@pytest.mark.parametrize("nominated", [0, 3],
                         ids=["batch-alone", "nominated-pods-behind-it"])
def test_steady_churn_at_a_batch_of_1024_dispatches_one_bucket_pair(
        nominated):
    """Forty refreshes of 900-1,150 arrivals and as many departures, the
    1,024 pending pods handed in: after the first every scatter runs at
    ONE (Dn, Dp).  The changed rows alone wander across 1,024 and 2,048;
    the floor of four times the batch's bucket holds them in 4,096.  The
    nominated pods the scheduler hands in behind a FULL batch (a
    preemption wave under way) are interned and move no floor: 1,027
    pending would bucket to 2,048 and send the same rows at 8,192."""
    rng = random.Random(44)
    cache = SchedulerCache()
    nodes = hollow.make_nodes(1600, zones=3)
    for n in nodes:
        cache.add_node(n)
    seq = 0

    def arrivals(k):
        nonlocal seq
        out = []
        for _ in range(k):
            seq += 1
            p = plain_pod(f"churn-{seq}", rng.choice(nodes).name)
            cache.add_pod(p)
            out.append(p)
        return out
    arrivals(2000)                      # residents that stay
    last = arrivals(1024)
    from kubetpu.framework.types import PodInfo
    pending = [PodInfo(plain_pod(f"pending-{k}", "")) for k in range(1024)]
    said = {}
    if nominated:
        said = {"batch": len(pending)}
        pending = pending + [PodInfo(plain_pod(f"nominated-{k}", ""))
                             for k in range(nominated)]
    dt = DeltaTensorizer(resync_interval=1000)
    dt.refresh(snapshot_of(cache), pending=pending, **said)
    pairs, rows = [], []
    for _ in range(40):
        # the batch bound a cycle ago leaves, as the benchmark's client
        # deletes the oldest-bound; the next one binds
        for p in last:
            cache.remove_pod(p)
        last = arrivals(rng.randint(900, 1150))
        _, st = dt.refresh(snapshot_of(cache), pending=pending, **said)
        assert not st.resync, st.reason
        pairs.append(st.delta_buckets)
        rows.append(st.span_args["delta-build"]["pod_rows_seen"])
    assert set(pairs[1:]) == {(2048, 4096)}, sorted(set(pairs))
    # the case the floor is for: the counts do cross a pow2 edge
    assert min(rows) <= 1024 < max(rows), (min(rows), max(rows))
    assert_matches_fresh(dt, snapshot_of(cache))


def test_the_scheduler_hands_refresh_the_batch_apart_from_the_nominated(
        monkeypatch):
    """``_prepare_group`` interns the nominated pods with the batch's
    class representatives (PR 48: four label groups, four classes a batch)
    and says how many pods the batch is."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    seen = []
    orig = DeltaTensorizer.refresh

    def noted(self, node_infos, pending=(), donate=True, batch=None):
        seen.append((len(pending), batch))
        return orig(self, node_infos, pending=pending, donate=donate,
                    batch=batch)
    monkeypatch.setattr(DeltaTensorizer, "refresh", noted)
    store = ClusterStore()
    nodes = hollow.make_nodes(8, zones=4)
    for n in nodes:
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
        chain_cycles=False)
    sched = Scheduler(store, config=cfg, async_binding=False)
    sched.queue.add_nominated_pod(plain_pod("waits-for-its-victims", ""),
                                  nodes[0].name)
    for p in hollow.make_pods(20, group_labels=4):
        store.add(p)
    assert len(drain(sched)) == 20
    sched.close()
    assert [batch for _, batch in seen] == [8, 8, 4]
    assert [n for n, _ in seen] == [5, 5, 5]


def test_the_cycles_row_maps_are_the_tensorizers_and_a_copy(monkeypatch):
    """``pod_uid_list()`` and the row map a cycle derives from it when
    first asked are ``pod_row`` the other way round after free-row reuse,
    a pod-axis growth and a resync; the list a cycle took does not move
    when the next refresh runs."""
    from kubetpu.preemption import CycleContext
    w = DenseWorld(monkeypatch)

    def taken():
        uids = w.dt.pod_uid_list()
        ctx = CycleContext(w.dt.builder, w.dt.cluster, None,
                           snapshot_of(w.cache))
        ctx.pod_uids = uids
        assert ctx.pod_rows is None           # nothing derived unasked
        assert ctx.pod_row_map() == w.dt.pod_row
        assert ctx.pod_row_map() is ctx.pod_rows
        return uids, list(uids), ctx
    kept = [taken()]
    for steps in (_one_in_one_out, _the_pod_axis_grows,
                  _a_resync_in_the_middle):
        gen = steps(w)
        next(gen)
        while True:
            st = w.refresh()
            kept.append(taken())
            try:
                gen.send(st)
            except StopIteration:
                break
    assert len({len(uids) for uids, _, _ in kept}) == 2       # it grew
    for uids, as_taken, ctx in kept:
        assert uids == as_taken
        assert ctx.pod_rows == {u: r for r, u in enumerate(uids) if u}
    # the last cycle's copy is not the tensorizer's own list
    assert kept[-1][0] is not w.dt.row_uids


# ---------------------------------------------------------------------------
# compile-once contract


def test_delta_drain_compiles_scatter_once_per_bucket():
    """50-cycle delta drain under the sanitize watchdog: the scatter
    program (apply_cluster_delta) compiles AT MOST once per pow2 bucket
    — same-bucket deltas are pure jit-cache hits."""
    from kubetpu.utils.sanitize import sanitized

    cache, nodes, pods = build_cache(n_nodes=6, pods_per_node=2, zones=3)
    rng = random.Random(3)
    live = list(pods)
    with sanitized() as wd:
        dt = DeltaTensorizer(resync_interval=1000)
        dt.refresh(snapshot_of(cache))
        for seq in range(50):
            # alternate adds/removes so the pod axis never grows: every
            # cycle touches 1-2 nodes -> one [Dn=8, Dp=8] bucket
            if seq % 2 == 0 or not live:
                p = hollow.make_pod(f"cyc-{seq}")
                p.metadata.labels = {"app": f"group-{rng.randrange(3)}"}
                p.spec.node_name = rng.choice(nodes).name
                cache.add_pod(p)
                live.append(p)
            else:
                cache.remove_pod(live.pop(rng.randrange(len(live))))
            _, st = dt.refresh(snapshot_of(cache))
            assert not st.resync, st.reason
        apply_compiles = {k: c for k, c in wd.counts.items()
                         if "apply_cluster_delta" in k[0]}
        assert apply_compiles, "scatter program never compiled?"
        for key, count in apply_compiles.items():
            assert count == 1, (key, count)
        assert len(apply_compiles) <= 2, apply_compiles
        wd.assert_no_recompilation()


# ---------------------------------------------------------------------------
# the serving loop rides the delta path


def drain(sched, max_cycles=12):
    out = []
    for _ in range(max_cycles):
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        out.extend(got)
    return out


def test_unchained_drain_builds_once(monkeypatch):
    """A multi-cycle gang drain with chaining OFF — the shape that used
    to re-tensorize the world every cycle — now runs ONE full build (the
    initial resync) and serves the rest by scatter."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    from kubetpu.state import tensors as tensors_mod

    builds = [0]
    orig = tensors_mod.SnapshotBuilder.build

    def counted(self, *a, **kw):
        builds[0] += 1
        return orig(self, *a, **kw)
    monkeypatch.setattr(tensors_mod.SnapshotBuilder, "build", counted)

    store = ClusterStore()
    for n in hollow.make_nodes(8, zones=4):
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
        chain_cycles=False)
    sched = Scheduler(store, config=cfg, async_binding=False)
    for p in hollow.make_pods(30, group_labels=4):
        store.add(p)
    out = drain(sched)
    assert len(out) == 30
    assert all(o.node for o in out), [(o.pod.metadata.name, o.err)
                                      for o in out if not o.node]
    # ONE build() walk — the initial resync; later resyncs (pod-axis
    # growth on the tiny starting bucket) re-upload without a walk
    assert builds[0] == 1, f"expected ONE initial resync, saw {builds[0]}"
    assert sched.resync_count >= 1
    assert len(sched.delta_rows) >= 1
    assert all(r > 0 for r in sched.delta_rows)
    sched.close()


def test_pipelined_drain_survives_mid_drain_chain_break():
    """The donation hazard: a pipelined drain has cycle k-1 dispatched but
    uncommitted when an external event breaks the chain, so cycle k's
    prepare runs a delta refresh — which must NOT donate the resident
    buffers k-1's commit-side device work (preemption wave, decision
    audit) still reads.  A foreign bound pod lands mid-drain; every
    pending pod must still commit exactly once."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler

    store = ClusterStore()
    for n in hollow.make_nodes(8, zones=4):
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
        chain_cycles=True, pipeline_cycles=True)
    sched = Scheduler(store, config=cfg, async_binding=False)
    for p in hollow.make_pods(32, group_labels=4):
        store.add(p)
    out = []
    foreign_landed = False
    for _ in range(20):
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        out.extend(got)
        if not foreign_landed:
            # a foreign writer binds a pod: chain dirty while a cycle is
            # in flight -> the next prepare takes the delta path
            foreign = hollow.make_pod("foreign-bound")
            foreign.spec.node_name = hollow.make_nodes(8)[3].name
            store.add(foreign)
            foreign_landed = True
    out.extend(sched.flush_pipeline())
    assert foreign_landed
    scheduled = [o for o in out if o.node]
    assert len(out) == 32, len(out)
    assert len(scheduled) == 32, [(o.pod.metadata.name, o.err)
                                  for o in out if not o.node]
    assert len({o.pod.uid for o in out}) == 32, "a pod committed twice"
    sched.close()


def test_depth4_chaos_dispatch_error_mid_drain():
    """Chaos at depth (extends the mid-drain chain-break regression):
    a seeded KUBETPU_CHAOS dispatch error fired mid-way through a
    depth-4 pipelined drain — with multiple cycles dispatched but
    uncommitted — must recover like the 2-deep chain did: every pod
    still binds EXACTLY once, and the recovery is auditable (no AOT
    runtime is armed here, so the ladder has nothing to demote)."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import chaos

    class CountingStore(ClusterStore):
        def __init__(self):
            super().__init__()
            self.bind_calls = []

        def bind(self, pod, node_name):
            self.bind_calls.append(pod.metadata.name)
            super().bind(pod, node_name)

    chaos.disarm()
    store = CountingStore()
    for n in hollow.make_nodes(8, zones=4):
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
        chain_cycles=True, pipeline_cycles=True, pipeline_depth=4,
        pod_initial_backoff_seconds=0.01, pod_max_backoff_seconds=0.05)
    sched = Scheduler(store, config=cfg, async_binding=False)
    try:
        for p in hollow.make_pods(48, group_labels=4):
            store.add(p)
        out = []
        # prime the ring: cycles dispatched-but-uncommitted, with a
        # backlog still queued behind them
        out.extend(sched.schedule_pending(timeout=0.0))
        assert len(sched._pipeline.ring) >= 1
        assert len(sched.queue) > 0
        # ...then the device dies under cycle j's dispatch
        chaos.arm(chaos.ChaosRegistry(seed=11).arm_point(
            "dispatch", "error", n=1))
        idle = 0
        while idle < 6:
            sched.queue.flush_backoff_completed()
            got = sched.schedule_pending(timeout=0.0)
            if got:
                out.extend(got)
                idle = 0
            else:
                got = sched.flush_pipeline()
                if got:
                    out.extend(got)
                    idle = 0
                else:
                    idle += 1
                    time.sleep(0.02)
        placed = {o.pod.uid for o in out if o.node}
        assert len(placed) == 48, f"{len(placed)} of 48 placed"
        # exactly once: the bind oracle saw each pod one time
        assert len(store.bind_calls) == len(set(store.bind_calls)) == 48
        assert any(e["kind"] == "dispatch-error"
                   for e in sched.recovery_log)
        assert sched.recovery_log[0]["demoted"] == []
    finally:
        chaos.disarm()
        sched.close()


def test_depth4_deadline_stall_reruns_younger_inflight_cycles(monkeypatch):
    """Scatter recovery at depth: a seeded KUBETPU_CHAOS dispatch STALL
    on cycle j of a depth-4 drain blows the dispatch deadline at j's
    readback — j's pods requeue, and every YOUNGER in-flight cycle is
    discarded and re-prepared against a fresh snapshot (the executor's
    rerun counter proves it); every pod still binds exactly once.  The
    compile-activity deadline exemption is pinned off (constant
    snapshots) so the injected stall — not compile noise — trips the
    deadline deterministically."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import chaos
    from kubetpu.utils import sanitize

    class _FrozenTimer:
        def snapshot(self):
            return {}

    monkeypatch.setattr(sanitize, "install_compile_timer",
                        lambda: _FrozenTimer())
    chaos.disarm()
    store = ClusterStore()
    for n in hollow.make_nodes(8, zones=4):
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
        chain_cycles=True, pipeline_cycles=True, pipeline_depth=4,
        dispatch_deadline_seconds=0.3,
        pod_initial_backoff_seconds=0.01, pod_max_backoff_seconds=0.05)
    sched = Scheduler(store, config=cfg, async_binding=False)
    try:
        for p in hollow.make_pods(48, group_labels=4):
            store.add(p)
        out = []
        for _ in range(3):
            out.extend(sched.schedule_pending(timeout=0.0))
        # the stall: cycle j's dispatch hangs ~1 s — far past the 0.3 s
        # deadline its own readback is measured against
        chaos.arm(chaos.ChaosRegistry(seed=7).arm_point(
            "dispatch", "stall", n=1, delay=1.0))
        idle = 0
        while idle < 6:
            sched.queue.flush_backoff_completed()
            got = sched.schedule_pending(timeout=0.0)
            if got:
                out.extend(got)
                idle = 0
            else:
                got = sched.flush_pipeline()
                if got:
                    out.extend(got)
                    idle = 0
                else:
                    idle += 1
                    time.sleep(0.02)
        placed = {o.pod.uid for o in out if o.node}
        assert len(placed) == 48, f"{len(placed)} of 48 placed"
        assert any(e["kind"] == "dispatch-deadline"
                   for e in sched.recovery_log), sched.recovery_log
        assert sched._pipeline.reruns >= 1, \
            "no younger in-flight cycle was re-prepared by scatter"
    finally:
        chaos.disarm()
        sched.close()


def test_depth4_donation_withheld_while_ring_uncommitted():
    """The generalized donation rule: with a depth-4 ring holding
    multiple dispatched-but-uncommitted cycles, a chain break's delta
    refresh must run donate=False whenever ANY in-flight cycle's cluster
    IS the resident (its commit-side preemption wave / decision audit
    still reads those buffers).  A foreign bound pod lands mid-drain to
    force the delta path while the ring is populated."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler

    store = ClusterStore()
    for n in hollow.make_nodes(8, zones=4):
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
        chain_cycles=True, pipeline_cycles=True, pipeline_depth=4)
    sched = Scheduler(store, config=cfg, async_binding=False)
    refreshes = []          # (donate, uncommitted-on-resident, ring len)
    orig_refresh = DeltaTensorizer.refresh

    def spy(self, node_infos, pending=(), donate=True, **kw):
        on_resident = sum(
            1 for p in sched._pipeline.ring.preps()
            if p.cluster is self.cluster)
        refreshes.append((donate, on_resident,
                          len(sched._pipeline.ring)))
        return orig_refresh(self, node_infos, pending=pending,
                            donate=donate, **kw)

    DeltaTensorizer.refresh = spy
    try:
        for p in hollow.make_pods(32, group_labels=4):
            store.add(p)
        out = []
        foreigns = 0
        for _ in range(30):
            got = sched.schedule_pending(timeout=0.0)
            out.extend(got)
            if foreigns < 4 and len(sched._pipeline.ring) >= 1:
                # a foreign writer binds a pod: chain dirty while
                # cycles are in flight -> the next prepare takes the
                # delta path against a populated ring.  Repeated so at
                # least one break catches a DELTA-prepared cycle (whose
                # cluster IS the resident) still uncommitted in the ring
                foreign = hollow.make_pod(f"foreign-{foreigns}")
                foreign.spec.node_name = hollow.make_nodes(8)[3].name
                store.add(foreign)
                foreigns += 1
        out.extend(sched.flush_pipeline())
        out.extend(_drain_sched(sched))
        assert foreigns >= 2
        assert len({o.pod.uid for o in out if o.node}) == 32
        # every refresh that ran while an uncommitted cycle sat on the
        # resident cluster withheld donation; refreshes with a clear
        # ring (or chained in-flight cycles only) donated
        assert refreshes, "no delta refresh ran"
        withheld = [r for r in refreshes if r[1] > 0]
        assert withheld, f"no refresh saw an uncommitted resident: " \
                         f"{refreshes}"
        assert all(r[0] is False for r in withheld), refreshes
        assert all(r[0] is True for r in refreshes if r[1] == 0), refreshes
    finally:
        DeltaTensorizer.refresh = orig_refresh
        sched.close()


def _drain_sched(sched, max_cycles=30):
    out = []
    for _ in range(max_cycles):
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        out.extend(got)
    out.extend(sched.flush_pipeline())
    return out


def test_flight_recorder_surfaces_delta_spans():
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import trace as utrace

    fr = utrace.arm_flight_recorder(capacity=16)
    fr.clear()
    try:
        store = ClusterStore()
        for n in hollow.make_nodes(4, zones=2):
            store.add(n)
        sched = Scheduler(store, config=KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
            chain_cycles=False), async_binding=False)
        for p in hollow.make_pods(12, group_labels=2):
            store.add(p)
        drain(sched)
        recs = fr.cycles()
        assert recs
        names = [s.name for r in recs for s in r.spans()]
        assert "resync" in names          # the initial build
        assert "delta-apply" in names     # later cycles scatter
        metas = [r.meta for r in recs if "delta_rows" in r.meta]
        assert metas
        # resync instants ride the chrome export as ph:"i" events
        resync_events = [e for r in recs for e in r.events()
                         if e["name"] == "resync"]
        assert resync_events and resync_events[0]["args"]["reason"]
        # traceview's stage table digest line
        import tools.traceview as tv
        spans = tv._load_spans(fr.to_pipeline_doc())
        digest = tv.delta_summary(spans)
        assert "delta cycles" in digest and "resyncs" in digest
        assert tv.delta_summary([]) == ""
        sched.close()
    finally:
        utrace.disarm_flight_recorder()
