"""The worlds in which the incoming REQUIRED pod-affinity filter BITES,
the program's gang cycle against the plain reference
(``perfbench/reference/interpod_required.py``, which is
``interpod_terms`` plus one switch).

``sp-podaffinity-5000`` (PR 46) labels every node ``zone1`` as upstream
does, so its own ``correct`` cannot see a node wrongly ADMITTED.  These
five worlds hold that instead, each with the line of v1.19
``interpodaffinity/filtering.go`` it stands on:

  (i)   three zones, the blue pods bound in one          :342 satisfyPodAffinity,
        (as owners of the term, and as bare labels)       :371-396 the
                                                          Unresolvable status
  (ii)  the same with nodes of 1,000m: the zone fills    :342, beside
        over four rounds and the rest stay pending        NodeResourcesFit
  (iii) an EMPTY cluster                                  :356-366 the
                                                          self-match bootstrap
  (iv)  a pod with TWO required terms                     :198 the PreFilter's
                                                          podMatchesAllAffinityTerms
                                                          (1.19's match-ALL rule)
  (v)   blue pods and plain pods in one batch             :342 for the one,
                                                          nothing for the other

The records are ``lib/world.py``'s, the cycle is ``lib/check.py``'s
(``program_gang_cycle``: the serving path's own Scheduler, one cycle),
the judge is the reference's ``gang_misses``; every limit is 0.  No
clock and no collector state is read.
"""

import collections
import dataclasses
import os
import sys
from types import SimpleNamespace

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from perfbench.lib import check, world  # noqa: E402
from perfbench.reference import interpod_required as ref  # noqa: E402
from perfbench.tools import cell_controls  # noqa: E402

ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
MI = 1 << 20
BLUE = (("color", "blue"),)
TERM = {"aff_required": ((ZONE, BLUE),)}
SEEDS = (46, 2 ** 31 + 46)


_control = cell_controls.control_module


def _nodes(per_zone, zones=3, cpu=4000):
    """``per_zone`` nodes in each of ``zones`` zones, node i in zone
    i % zones (``zone1``...), as ``labelNodePrepareStrategy`` deals them."""
    return [world.NodeRec(f"node-{i}", cpu, 32 * 1024 * MI, 110,
                          {HOSTNAME: f"node-{i}",
                           ZONE: f"zone{i % zones + 1}"})
            for i in range(per_zone * zones)]


def _zone_of(nodes):
    return {n.name: n.labels[ZONE] for n in nodes}


def _pod(name, labels=None, **terms):
    return world.PodRec(name, 100, 500 * MI, 0, dict(labels or {}), **terms)


def _blue(name, owner=True):
    return _pod(name, {"color": "blue"}, **(TERM if owner else {}))


def _cell(batch):
    return SimpleNamespace(
        name="zones.closed", traffic={"resident_bound": 0},
        config={"scheduler": {"mode": "gang", "batch_size": batch},
                "mesh_shape": None},
        reference=lambda: ref)


def _cycle(nodes, bound, sample, seed=46, batch=None):
    """One gang cycle of the program over the hand-made cluster:
    (placements, the meta of the ONE cycle that ran an auction)."""
    from kubetpu.utils import trace as utrace
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        placed = check.program_gang_cycle(
            _cell(batch or len(sample)), seed, nodes, bound, sample)
        cycles = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    ran = [c["meta"] for c in cycles if c["meta"].get("auction_rounds")]
    assert len(ran) == 1, [c["meta"] for c in cycles]
    return placed, ran[0]


def _judge(nodes, bound):
    cluster = ref.Cluster(nodes)
    for rec, node in bound:
        cluster.add(rec, node)
    return cluster


def _zones(nodes, placed):
    zone = _zone_of(nodes)
    return collections.Counter(zone[n] if n else "pending"
                               for n in placed.values())


def _home_world(owner, per_zone=4, cpu=4000, per_node=2):
    """Three zones; ``per_node`` blue pods on every node of zone1 and
    nothing else bound."""
    nodes = _nodes(per_zone, cpu=cpu)
    home = [n.name for n in nodes if n.labels[ZONE] == "zone1"]
    bound = [(_blue(f"b-{i}-{j}", owner), name)
             for i, name in enumerate(home) for j in range(per_node)]
    return nodes, bound


# ------------------------------------------------------------ world (i)

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("owner", [True, False], ids=["owners", "labels"])
def test_i_every_placement_lies_in_the_zone_of_the_blue_pods(owner, seed):
    """filtering.go:342: a node passes only if its zone holds a pod that
    matches the term.  LeastAllocated alone would send every pod to the
    sixteen EMPTY nodes of zone2 and zone3."""
    nodes, bound = _home_world(owner)
    sample = [_blue(f"p{i}") for i in range(32)]
    placed, meta = _cycle(nodes, bound, sample, seed)
    assert _zones(nodes, placed) == {"zone1": 32}
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    assert meta["term_sets_live"] == ["ra"]
    assert meta["required_affinity_terms"] == 32
    assert meta["score_terms_spliced"] == 32
    # matches exist: nobody came in by the bootstrap, nobody deferred
    assert meta["affinity_bootstrap_admits"] == 0
    assert meta["auction_rounds"] == 2      # the second admits nobody
    # the reference's own auction lands in the same zone
    got = ref.auction_schedule(_judge(nodes, bound), sample,
                               np.random.default_rng(seed))
    assert _zones(nodes, got) == {"zone1": 32}


def test_i_the_other_zones_nodes_are_unresolvable_not_just_unschedulable():
    """filtering.go:371-396: a node refused for the pod's required
    affinity is UnschedulableAndUnresolvable (no preemption there can
    help).  Read off the auction's own diagnostic mask."""
    import jax
    from tests.test_gang import build
    nodes, bound = _home_world(owner=True)
    existing = collections.defaultdict(list)
    for rec, node in bound:
        existing[node].append(world.api_pod(rec))
    api_nodes = [world.api_node(n) for n in nodes]
    pending = [world.api_pod(_blue(f"p{i}")) for i in range(4)]
    from kubetpu.models import gang
    cluster, batch, cfg, names = build(
        api_nodes, existing, pending,
        filters=("NodeResourcesFit", "InterPodAffinity"))
    g = gang.schedule_gang(cluster, batch, cfg, jax.random.PRNGKey(46))
    zone = _zone_of(nodes)
    home = np.array([zone[n] == "zone1" for n in names])
    unres = np.asarray(g.unresolvable)[:4, :len(names)]
    assert unres[:, ~home].all() and not unres[:, home].any()
    assert home[np.asarray(g.chosen)[:4]].all()
    assert int(g.affinity_bootstrap_admits) == 0


@pytest.mark.parametrize("seed", SEEDS)
def test_i_the_control_fails_where_the_filter_alone_holds_the_zone(seed):
    """``no-required-affinity`` (every node satisfies the incoming term).
    Where the bound blue pods carry LABELS only, nothing but the filter
    holds a pod to zone1 and the control scatters the batch over the
    empty zones: it FAILS.  Where they OWN the term, each is a score row
    at hardPodAffinityWeight 1 (scoring.go processExistingPod), 100
    points for zone1 against LeastAllocated's handful, and the control
    changes nothing: what the hard weight hides."""
    mod = _control("no-required-affinity")
    sample = [_blue(f"p{i}") for i in range(32)]
    for owner, fails in ((False, True), (True, False)):
        nodes, bound = _home_world(owner)
        with mod.program_control():
            placed, _ = _cycle(nodes, bound, sample, seed)
        misses = ref.gang_misses(_judge(nodes, bound), sample, placed)
        by_ref = ref.auction_schedule(
            _judge(nodes, bound), sample, np.random.default_rng(seed),
            **mod.REFERENCE_KW)
        ref_misses = ref.gang_misses(_judge(nodes, bound), sample, by_ref)
        if fails:
            assert _zones(nodes, placed)["zone1"] == 0
            assert len(misses) == 32 and "infeasible" in misses[0]
            assert len(ref_misses) == 32
        else:
            assert _zones(nodes, placed) == {"zone1": 32}
            assert misses == [] and ref_misses == []


# ----------------------------------------------------------- world (ii)

@pytest.mark.parametrize("seed", SEEDS)
def test_ii_the_zone_fills_and_the_rest_stay_pending(seed):
    """Nodes of 1,000m hold ten pods.  zone1's four nodes hold 1, 3, 5
    and 7 blue pods: 24 slots for a batch of 40.  Every round the pods
    left over crowd the emptiest node and capacity ends the round (9,
    then 7, 5 and 3 admitted); the zone fills to the last slot and
    sixteen pods stay pending, though eight empty nodes stand in the
    other zones."""
    nodes = _nodes(4, cpu=1000)
    home = [n.name for n in nodes if n.labels[ZONE] == "zone1"]
    bound = [(_blue(f"b-{i}-{j}"), name) for i, name in enumerate(home)
             for j in range(2 * i + 1)]
    sample = [_blue(f"p{i}") for i in range(40)]
    placed, meta = _cycle(nodes, bound, sample, seed)
    assert _zones(nodes, placed) == {"zone1": 24, "pending": 16}
    per_node = collections.Counter(n for n in placed.values() if n)
    assert [per_node[name] for name in home] == [9, 7, 5, 3]
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    assert meta["auction_rounds"] == 5      # four that admit, one that ends
    assert meta["capacity_deferred"] == 31 + 24 + 19 + 16
    assert meta["affinity_bootstrap_admits"] == 0
    # the reference's own auction fills the zone and leaves as many over
    got = ref.auction_schedule(_judge(nodes, bound), sample,
                               np.random.default_rng(seed))
    assert _zones(nodes, got) == {"zone1": 24, "pending": 16}


# ---------------------------------------------------------- world (iii)

@pytest.mark.parametrize("seed", SEEDS)
def test_iii_an_empty_cluster_is_entered_by_one_pod_and_followed(seed):
    """filtering.go:356-366: no pod anywhere matches, the pod matches
    its own term, so every node that carries the key passes.  That is
    true of EVERY pod of the batch as round 1 starts; the serial loop
    lets the first in and the second already finds a match, in the
    first's zone.  The auction admits one pod by the bootstrap, defers
    every other behind it and places them in ITS zone in round 2."""
    nodes = _nodes(8)
    sample = [_blue(f"p{i}") for i in range(32)]
    placed, meta = _cycle(nodes, [], sample, seed)
    zones = _zones(nodes, placed)
    assert len(zones) == 1 and "pending" not in zones, zones
    assert meta["affinity_bootstrap_admits"] == 1
    assert meta["auction_rounds"] == 3      # 1, then 31, then nobody
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []
    got = ref.auction_schedule(_judge(nodes, []), sample,
                               np.random.default_rng(seed))
    assert len(_zones(nodes, got)) == 1


@pytest.mark.parametrize("seed", SEEDS)
def test_iii_the_blind_batch_bootstraps_everyone_at_once_and_fails(seed):
    """``blind-batch`` (the batch's own pods left out of the term
    filter): every pod passes by the bootstrap against the empty cluster
    and LeastAllocated scatters them over the three zones."""
    nodes = _nodes(8)
    sample = [_blue(f"p{i}") for i in range(32)]
    with _control("blind-batch").program_control():
        placed = check.program_gang_cycle(_cell(32), seed, nodes, [],
                                          sample)
    assert len(_zones(nodes, placed)) == 3
    misses = ref.gang_misses(_judge(nodes, []), sample, placed)
    assert len(misses) >= 16 and "infeasible" in misses[0]
    by_ref = ref.auction_schedule(_judge(nodes, []), sample,
                                  np.random.default_rng(seed),
                                  blind_batch=True)
    assert len(ref.gang_misses(_judge(nodes, []), sample, by_ref)) >= 16


def test_iii_a_pod_that_does_not_match_itself_has_no_bootstrap():
    """filtering.go:356: the rule asks that the pod match its OWN terms.
    A plain pod that requires a blue peer stays pending on an empty
    cluster, and is placed once a blue pod is bound."""
    nodes = _nodes(2)
    lonely = [_pod("lonely", **TERM)]
    placed, meta = _cycle(nodes, [], lonely)
    assert placed == {"lonely": ""}
    assert meta["affinity_bootstrap_admits"] == 0
    assert ref.gang_misses(_judge(nodes, []), lonely, placed) == []
    bound = [(_blue("b", owner=False), "node-1")]      # zone2
    placed, _ = _cycle(nodes, bound, lonely)
    assert _zones(nodes, placed) == {"zone2": 1}


# ----------------------------------------------------------- world (iv)

DB = (("tier", "db"),)
TWO_TERMS = {"aff_required": ((ZONE, BLUE), (ZONE, DB))}


def test_iv_two_terms_need_one_pod_that_matches_both():
    """1.19 counts an existing pod for the incoming pod's affinity only
    if it matches ALL of its terms (filtering.go:198,
    podMatchesAllAffinityTerms).  zone1 holds a blue pod AND a db pod,
    zone2 holds one pod that is both: a per-term reading admits zone1
    and zone2, upstream admits zone2 alone; without the one pod that is
    both, nothing (the pod does not match itself: no bootstrap)."""
    nodes = _nodes(2)
    apart = [(_pod("blue", {"color": "blue"}), "node-0"),
             (_pod("db", {"tier": "db"}), "node-3")]            # zone1
    both = (_pod("both", {"color": "blue", "tier": "db"}), "node-1")
    sample = [_pod(f"p{i}", **TWO_TERMS) for i in range(4)]
    placed, meta = _cycle(nodes, apart, sample)
    assert set(placed.values()) == {""}
    assert meta["required_affinity_terms"] == 8
    assert ref.gang_misses(_judge(nodes, apart), sample, placed) == []
    placed, meta = _cycle(nodes, apart + [both], sample)
    assert _zones(nodes, placed) == {"zone2": 4}
    assert ref.gang_misses(_judge(nodes, apart + [both]), sample,
                           placed) == []
    assert meta["affinity_bootstrap_admits"] == 0


def test_iv_two_terms_on_a_pod_that_is_both_bootstrap_together():
    """The bootstrap under the same rule: no pod matches BOTH terms, the
    incoming pods do, so the first enters an otherwise mismatched
    cluster by filtering.go:356 and the rest follow it."""
    nodes = _nodes(2)
    apart = [(_pod("blue", {"color": "blue"}), "node-0"),
             (_pod("db", {"tier": "db"}), "node-3")]
    sample = [_pod(f"p{i}", {"color": "blue", "tier": "db"}, **TWO_TERMS)
              for i in range(6)]
    placed, meta = _cycle(nodes, apart, sample)
    zones = _zones(nodes, placed)
    assert len(zones) == 1 and "pending" not in zones
    assert meta["affinity_bootstrap_admits"] == 1
    assert ref.gang_misses(_judge(nodes, apart), sample, placed) == []


# ------------------------------------------------------------ world (v)

@pytest.mark.parametrize("seed", SEEDS)
def test_v_blue_pods_and_plain_pods_in_one_batch(seed):
    """The blue pods of a mixed batch keep to zone1; the plain ones,
    which no term selects and which carry none, go where LeastAllocated
    sends them: the empty nodes of the other zones."""
    nodes, bound = _home_world(owner=True)
    sample = [(_blue if i % 2 == 0 else _pod)(f"p{i}") for i in range(32)]
    placed, meta = _cycle(nodes, bound, sample, seed)
    zone = _zone_of(nodes)
    blue = {zone[placed[p.name]] for p in sample if p.labels}
    plain = collections.Counter(zone[placed[p.name]] for p in sample
                                if not p.labels)
    assert blue == {"zone1"}
    assert plain["zone1"] == 0 and sum(plain.values()) == 16
    assert meta["required_affinity_terms"] == 16
    assert meta["score_terms_spliced"] == 16
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []


# -------------------------------------------- the reference's own switch

def test_the_references_switch_opens_the_affinity_filter_and_nothing_else():
    nodes, bound = _home_world(owner=False)
    cluster = _judge(nodes, bound)
    home = np.array([n.labels[ZONE] == "zone1" for n in nodes])
    pod = _blue("p")
    assert (cluster.terms_ok(pod) == home).all()
    cluster.no_required_affinity = True
    assert cluster.terms_ok(pod).all() and cluster.terms_ok(pod, 1) is True
    # a required ANTI-affinity term still filters under the switch
    anti = dataclasses.replace(pod, anti_required=((ZONE, BLUE),))
    assert (cluster.terms_ok(anti) == ~home).all()
    cluster.no_required_affinity = False
    assert not cluster.terms_ok(anti).any()
    # with the switch off the file IS interpod_terms
    from perfbench.reference import interpod_terms
    plain = interpod_terms.Cluster(nodes)
    for rec, node in bound:
        plain.add(rec, node)
    assert (plain.terms_ok(pod) == cluster.terms_ok(pod)).all()
    assert (plain.scores(pod) == cluster.scores(pod)).all()
    assert ref.gang_misses is interpod_terms.gang_misses
