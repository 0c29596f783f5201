"""The worlds in which NodeAffinity's REQUIRED filter BITES, the
program's gang cycle against the plain reference
(``perfbench/reference/node_affinity.py``).

``sp-nodeaffinity-5000`` (PR 49) labels every node ``zone1`` as upstream
does and its term lists ``zone1`` and ``zone2``, so the real filter
admits every node and the row's own ``correct`` cannot see a node wrongly
ADMITTED.  These six worlds hold that instead, each with the upstream
rule it stands on (v1.19 ``nodeaffinity/node_affinity.go``,
``plugins/helper/node_affinity.go``, ``v1helper.MatchNodeSelectorTerms``):

  (i)   three zones, the term lists two                 node_affinity.go:54 Filter;
        (placements, and the auction's own mask)         :60 UnschedulableAndUnresolvable
  (ii)  the third zone EMPTY, which LeastAllocated       the same, against
        prefers: ``no-node-affinity`` sends the batch    NodeResourcesLeastAllocated
        there
  (iii) ONE zone (the row in small): ``In`` read as      labels.Selector In:
        "every listed value" refuses every node          set.Has(ls.Get(key))
  (iv)  values no node carries; nodes that lack the      In needs ls.Has(key);
        key: refused, the pods stay pending              an unknown value matches nothing
  (v)   term pods and plain pods in ONE batch (two       affinity == nil passes
        classes, one built row each, gathered)           every node
  (vi)  a node relabelled between two cycles, through    the snapshot's labels,
        the DeltaTensorizer's scatter                    not the first build's

The records are ``lib/world.py``'s, the cycle is ``lib/check.py``'s
(``program_gang_cycle``: the serving path's own Scheduler, one cycle),
the judge is the reference's ``gang_misses``; every limit is 0.  No
clock and no collector state is read.
"""

import collections
import dataclasses
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.lib import check, world  # noqa: E402
from perfbench.reference import node_affinity as ref  # noqa: E402
from perfbench.tools import cell_controls  # noqa: E402
from test_required_affinity_zone import (  # noqa: E402
    _cell, _nodes, _pod, _zone_of, _zones)

ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
IN_TWO = {"node_affinity_in": ((ZONE, ("zone1", "zone2")),)}
SEEDS = (49, 2 ** 31 + 49)
NO_NA, EVERY = "no-node-affinity", "in-needs-every-value"

_control = cell_controls.control_module


def _term(name, **labels):
    return _pod(name, labels, **IN_TWO)


def _cycle(nodes, bound, sample, seed=49):
    """One gang cycle of the program over the hand-made cluster:
    (placements, the records of the cycles that ran an auction)."""
    from kubetpu.utils import trace as utrace
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        placed = check.program_gang_cycle(
            _cell(len(sample)), seed, nodes, bound, sample)
        cycles = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    ran = [c for c in cycles if c["meta"].get("auction_rounds")]
    assert len(ran) == 1, [c["meta"] for c in cycles]
    return placed, ran[0]


def _build_args(record):
    build, = [s for s in record["spans"] if s["name"] == "batch-build"]
    return build["args"]


def _judge(nodes, bound):
    cluster = ref.Cluster(nodes)
    for rec, node in bound:
        cluster.add(rec, node)
    return cluster


def _by_reference(nodes, bound, sample, seed, **control):
    """(placements of the reference's own auction, their misses)."""
    got = ref.auction_schedule(_judge(nodes, bound), sample,
                               np.random.default_rng(seed), **control)
    return got, ref.gang_misses(_judge(nodes, bound), sample, got)


def _filled(per_zone=4, per_node=2, empty="zone3"):
    """Three zones; ``per_node`` plain pods on every node outside the
    ``empty`` zone and nothing bound inside it."""
    nodes = _nodes(per_zone)
    bound = [(_pod(f"b-{n.name}-{j}"), n.name) for n in nodes
             if n.labels[ZONE] != empty for j in range(per_node)]
    return nodes, bound


# ------------------------------------------------------------ world (i)

@pytest.mark.parametrize("seed", SEEDS)
def test_i_no_placement_in_the_zone_the_term_does_not_list(seed):
    """node_affinity.go:54: a node passes only if it matches the pod's
    required node selector.  Nothing is bound, so every node scores
    alike and a third of the batch would land in zone3."""
    nodes = _nodes(4)
    sample = [_term(f"p{i}") for i in range(32)]
    placed, record = _cycle(nodes, [], sample, seed)
    zones = _zones(nodes, placed)
    assert set(zones) == {"zone1", "zone2"} and sum(zones.values()) == 32
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []
    meta = record["meta"]
    assert meta["node_affinity_terms"] == 32
    assert meta["node_affinity_unique_selectors"] == 1
    assert _build_args(record)["rna_rows"] == 32
    assert _build_args(record)["rna_unique"] == 1
    # one class of pods, one row built for all 32
    assert meta["pod_classes"] == 1 and meta["rows_built"] == 1
    assert meta["term_sets_live"] == []
    got, misses = _by_reference(nodes, [], sample, seed)
    assert misses == [] and set(_zones(nodes, got)) == {"zone1", "zone2"}


def test_i_the_refused_zones_nodes_are_unresolvable_not_just_unschedulable():
    """node_affinity.go:60: a node refused by the required node selector
    is UnschedulableAndUnresolvable (no preemption there can help).  Read
    off the auction's own diagnostic mask."""
    import jax
    from kubetpu.models import gang
    from tests.test_gang import build
    nodes = _nodes(4)
    api_nodes = [world.api_node(n) for n in nodes]
    pending = [world.api_pod(_term(f"p{i}")) for i in range(4)]
    cluster, batch, cfg, names = build(
        api_nodes, {}, pending, filters=("NodeResourcesFit", "NodeAffinity"))
    assert np.asarray(batch.has_rna)[:4].all()
    assert int(np.asarray(batch.rna_valid).sum()) == 4
    assert int(np.asarray(batch.rna_sel.sel_valid).sum()) == 1
    g = gang.schedule_gang(cluster, batch, cfg, jax.random.PRNGKey(49))
    zone = _zone_of(nodes)
    refused = np.array([zone[n] == "zone3" for n in names])
    unres = np.asarray(g.unresolvable)[:4, :len(names)]
    assert unres[:, refused].all() and not unres[:, ~refused].any()
    assert not refused[np.asarray(g.chosen)[:4]].any()


# ----------------------------------------------------------- world (ii)

@pytest.mark.parametrize("seed", SEEDS)
def test_ii_the_empty_zone_draws_the_batch_only_under_the_control(seed):
    """zone3's nodes are EMPTY: NodeResourcesLeastAllocated scores them
    above every node of zone1 and zone2, and the filter refuses them.
    The program reads 0; ``no-node-affinity`` (``has_rna`` all False)
    sends the whole batch to zone3 and every placement is a miss, in the
    program's place and in the reference's."""
    nodes, bound = _filled()
    sample = [_term(f"p{i}") for i in range(32)]
    placed, _ = _cycle(nodes, bound, sample, seed)
    assert "zone3" not in _zones(nodes, placed)
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    got, misses = _by_reference(nodes, bound, sample, seed)
    assert misses == [] and "zone3" not in _zones(nodes, got)
    mod = _control(NO_NA)
    with mod.program_control():
        opened, _ = _cycle(nodes, bound, sample, seed)
    assert _zones(nodes, opened) == {"zone3": 32}
    misses = ref.gang_misses(_judge(nodes, bound), sample, opened)
    assert len(misses) == 32 and "infeasible" in misses[0]
    got, misses = _by_reference(nodes, bound, sample, seed,
                                **mod.REFERENCE_KW)
    assert _zones(nodes, got) == {"zone3": 32} and len(misses) == 32


# ---------------------------------------------------------- world (iii)

@pytest.mark.parametrize("seed", SEEDS)
def test_iii_in_read_as_every_value_refuses_the_one_zone_row(seed):
    """The row in small: every node ``zone1``, the term lists ``zone1``
    and ``zone2`` (a value NO node carries: the compiler's lookup reads
    -1 and leaves it out).  ``In`` is "one of": every node passes.  Read
    as "every one of" no node does: the whole batch stays pending and
    the reference, which can place it, says so of every pod; the
    filter's own control cannot fail here (every node passes anyway)."""
    nodes = _nodes(12, zones=1)
    bound = [(_term(f"b-{n.name}"), n.name) for n in nodes]
    sample = [_term(f"p{i}") for i in range(32)]
    placed, record = _cycle(nodes, bound, sample, seed)
    assert _zones(nodes, placed) == {"zone1": 32}
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    assert record["meta"]["node_affinity_terms"] == 32
    mod = _control(EVERY)
    with mod.program_control():
        broken, _ = _cycle(nodes, bound, sample, seed)
    assert set(broken.values()) == {""}
    misses = ref.gang_misses(_judge(nodes, bound), sample, broken)
    assert len(misses) == 32
    assert all("left pending, the reference can place it" in m
               for m in misses)
    got, misses = _by_reference(nodes, bound, sample, seed,
                                **mod.REFERENCE_KW)
    assert set(got.values()) == {""} and len(misses) == 32
    opened = _control(NO_NA)
    with opened.program_control():
        same, _ = _cycle(nodes, bound, sample, seed)
    assert ref.gang_misses(_judge(nodes, bound), sample, same) == []
    assert _by_reference(nodes, bound, sample, seed,
                         **opened.REFERENCE_KW)[1] == []


def test_iii_the_control_leaves_a_one_value_requirement_alone():
    """``in-needs-every-value`` changes only a requirement that lists
    several values: a term that lists ``zone2`` alone still admits
    zone2's nodes and no other."""
    nodes = _nodes(4)
    only = {"node_affinity_in": ((ZONE, ("zone2",)),)}
    sample = [_pod(f"p{i}", **only) for i in range(8)]
    with _control(EVERY).program_control():
        placed, _ = _cycle(nodes, [], sample)
    assert _zones(nodes, placed) == {"zone2": 8}
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []


# ----------------------------------------------------------- world (iv)

def _labelled(n, labels):
    """``n`` nodes that carry their hostname and ``labels``, no more."""
    return [dataclasses.replace(node,
                                labels={HOSTNAME: node.name, **labels})
            for node in _nodes(n, zones=1)]


@pytest.mark.parametrize("nodes", [
    pytest.param(_labelled(8, {ZONE: "zone3"}), id="values-no-node-carries"),
    pytest.param(_labelled(8, {}), id="nodes-lack-the-key")])
def test_iv_nothing_matches_and_everything_stays_pending(nodes):
    """No listed value is in the cluster's vocabulary (every lookup of
    the compiler reads -1), or no node carries the key at all: no node
    matches, on either side, and a pod nobody can place is no miss."""
    sample = [_term(f"p{i}") for i in range(8)]
    placed, record = _cycle(nodes, [], sample)
    assert set(placed.values()) == {""}
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []
    # the term is still a valid row: it matches nothing, it is not absent
    assert record["meta"]["node_affinity_terms"] == 8
    assert record["meta"]["node_affinity_unique_selectors"] == 1
    got, misses = _by_reference(nodes, [], sample, 49)
    assert set(got.values()) == {""} and misses == []
    assert not _judge(nodes, []).node_affinity_ok(sample[0]).any()


@pytest.mark.parametrize("seed", SEEDS)
def test_iv_a_node_that_lacks_the_key_is_refused_beside_one_that_has_it(
        seed):
    """Half the nodes carry ``zone1`` and two pods each, half carry no
    zone label and nothing: LeastAllocated prefers the bare ones, ``In``
    needs the key (``ls.Has(key)``)."""
    zoned = _nodes(6, zones=1)
    bare = [dataclasses.replace(n, name=f"bare-{i}",
                                labels={HOSTNAME: f"bare-{i}"})
            for i, n in enumerate(zoned)]
    nodes = zoned + bare
    bound = [(_pod(f"b-{n.name}-{j}"), n.name) for n in zoned
             for j in range(2)]
    sample = [_term(f"p{i}") for i in range(16)]
    placed, _ = _cycle(nodes, bound, sample, seed)
    assert all(node.startswith("node-") for node in placed.values())
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    with _control(NO_NA).program_control():
        opened, _ = _cycle(nodes, bound, sample, seed)
    assert all(node.startswith("bare-") for node in opened.values())
    assert len(ref.gang_misses(_judge(nodes, bound), sample, opened)) == 16


# ------------------------------------------------------------ world (v)

@pytest.mark.parametrize("seed", SEEDS)
def test_v_term_pods_and_plain_pods_in_one_batch(seed):
    """World (ii) with every other pod plain: two classes, one built row
    each, gathered out to the batch (PR 48): ``rna_valid`` / ``has_rna``
    of all 32 rows must be their own class's.  The plain pods go where
    LeastAllocated sends them, the empty zone3; no term pod does."""
    nodes, bound = _filled()
    sample = [(_term if i % 2 == 0 else _pod)(f"p{i}") for i in range(32)]
    placed, record = _cycle(nodes, bound, sample, seed)
    zone = _zone_of(nodes)
    term = collections.Counter(zone[placed[p.name]] for p in sample
                               if p.node_affinity_in)
    plain = collections.Counter(zone[placed[p.name]] for p in sample
                                if not p.node_affinity_in)
    assert term["zone3"] == 0 and sum(term.values()) == 16
    assert plain == {"zone3": 16}
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    meta, args = record["meta"], _build_args(record)
    assert meta["pod_classes"] == 2 and meta["rows_built"] == 2
    assert meta["node_affinity_terms"] == args["rna_rows"] == 16
    assert meta["node_affinity_unique_selectors"] == args["rna_unique"] == 1
    got, misses = _by_reference(nodes, bound, sample, seed)
    assert misses == []


def test_v_the_gathered_rows_are_the_per_pod_builds():
    """The class gather against ``_build_rows`` for the pods themselves:
    ``rna_valid``, ``has_rna`` and the slot index of ``rna_sel`` of all
    rows, padding included (the batch of 6 is padded to 8)."""
    from kubetpu.framework.types import PodInfo
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder
    pods = [PodInfo(world.api_pod((_term if i % 3 else _pod)(f"p{i}")))
            for i in range(6)]
    sb = SnapshotBuilder()
    sb.intern_pending(pods)
    pb = PodBatchBuilder(sb.table)
    shared = pb.build(pods)
    assert pb.pod_classes == 2 and pb.rows_built == 2
    each = pb._build_rows(pods, 8, [None] * 6)
    for leaf in ("rna_valid", "has_rna"):
        assert np.array_equal(getattr(shared, leaf), getattr(each, leaf))
    assert shared.has_rna.tolist() == [False, True, True, False, True, True,
                                       False, False]
    assert int(shared.rna_valid.sum()) == 4
    valid = np.asarray(shared.rna_valid).reshape(-1)
    assert np.array_equal(np.asarray(shared.rna_sel.index)[valid],
                          np.asarray(each.rna_sel.index)[valid])
    assert int(shared.rna_sel.sel_valid.sum()) \
        == int(each.rna_sel.sel_valid.sum()) == 1


# ----------------------------------------------------------- world (vi)

def test_vi_a_node_relabelled_between_two_cycles_is_refused_in_the_second():
    """Through the serving path's own DeltaTensorizer: cycle one places
    on every node of zone1 and zone2; then the EMPTIEST zone1 node is
    relabelled zone3 (a value the vocabulary already holds, so the delta
    path scatters the row and does not rebuild); cycle two must refuse
    it though LeastAllocated prefers it.  After it the resident tensors
    equal a fresh build's."""
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import trace as utrace
    from kubetpu.utils.metrics import SchedulerMetrics
    from test_delta import assert_matches_fresh, snapshot_of
    nodes = _nodes(4)
    moved = "node-0"                                   # zone1
    bound = [(_pod(f"b-{n.name}-{j}"), n.name) for n in nodes
             if n.name != moved for j in range(3)]
    store = world.build_store(nodes, bound)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    sched = Scheduler(
        store, config=world.scheduler_config(
            {"mode": "gang", "batch_size": 8}),
        metrics=SchedulerMetrics(), seed=49, async_binding=False)

    def cycle(recs):
        for rec in recs:
            store.add(world.api_pod(rec))
        while sched.schedule_pending(timeout=0.2):
            pass
        return {rec.name: store.get_pod("default", rec.name).spec.node_name
                or "" for rec in recs}
    try:
        first = cycle([_term(f"a{i}") for i in range(2)])
        # the empty zone1 node draws the first cycle's pods
        assert set(first.values()) == {moved}
        node = store.get_node(moved)
        node.metadata.labels[ZONE] = "zone3"
        store.update(node)
        second = cycle([_term(f"c{i}") for i in range(8)])
        records = [c.to_dict() for c in flight.cycles()]
        delta = next(iter(sched._delta.values()))
        infos = snapshot_of(sched.cache)
        delta.refresh(infos)
        assert_matches_fresh(delta, infos)
    finally:
        sched.close()
        utrace.disarm_flight_recorder()
    assert all(second.values()) and moved not in second.values()
    zone = dict(_zone_of(nodes), **{moved: "zone3"})
    assert {zone[n] for n in second.values()} <= {"zone1", "zone2"}
    ran = [c["meta"] for c in records if c["meta"].get("auction_rounds")]
    assert len(ran) == 2 and ran[1]["resync"] is False
    assert ran[1]["node_affinity_terms"] == 8
    # the reference, told of the new label, agrees
    after = [dataclasses.replace(n, labels={**n.labels, ZONE: "zone3"})
             if n.name == moved else n for n in nodes]
    now = bound + [(_term(name), node) for name, node in first.items()]
    sample = [_term(f"c{i}") for i in range(8)]
    assert ref.gang_misses(_judge(after, now), sample, second) == []
    # and told nothing, it would have preferred the moved node
    stale = ref.auction_schedule(_judge(nodes, now), sample,
                                 np.random.default_rng(49))
    assert moved in stale.values()


# ---------------------------------------------- the reference's switches

def test_the_references_switches_open_and_shut_the_filter_and_nothing_else():
    nodes, bound = _filled()
    cluster = _judge(nodes, bound)
    listed = np.array([n.labels[ZONE] != "zone3" for n in nodes])
    pod = _term("p")
    assert (cluster.node_affinity_ok(pod) == listed).all()
    assert (cluster.terms_ok(pod) == listed).all()
    assert cluster.terms_ok(pod, 2) is False and cluster.terms_ok(pod, 0)
    assert cluster.node_affinity_ok(_pod("plain")).all()
    cluster.no_node_affinity = True
    assert cluster.terms_ok(pod).all() and cluster.terms_ok(pod, 2) is True
    cluster.no_node_affinity = False
    cluster.in_needs_every_value = True
    assert not cluster.terms_ok(pod).any()
    # a required ANTI-affinity term still filters, with or without
    blue = (("color", "blue"),)
    cluster.in_needs_every_value = False
    cluster.add(_pod("blue", {"color": "blue"}), "node-0")
    anti = dataclasses.replace(pod, anti_required=((HOSTNAME, blue),))
    want = listed.copy()
    want[0] = False
    assert (cluster.terms_ok(anti) == want).all()
    # the scores are default_plugins', the term or no term
    from perfbench.reference import default_plugins
    base = default_plugins.Cluster(nodes)
    for rec, node in bound:
        base.add(rec, node)
    base.add(_pod("blue", {"color": "blue"}), "node-0")
    assert (base.scores(_pod("plain")) == cluster.scores(pod)).all()
    assert ref.gang_misses is default_plugins.gang_misses
    # auction_schedule leaves both switches off behind it
    ref.auction_schedule(cluster, [pod], np.random.default_rng(1),
                         no_node_affinity=True, in_needs_every_value=True)
    assert not cluster.no_node_affinity and not cluster.in_needs_every_value
