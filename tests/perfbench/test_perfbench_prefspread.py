"""The row PR 42 added, ``sp-prefspread-5000`` (upstream's
PreferredTopologySpreading: every measured pod carries one
``ScheduleAnyway`` zone constraint), and what holds it:

its reference (``perfbench/reference/topology_spread_soft.py``) against
hand-worked cases of ``podtopologyspread/scoring.go`` (the raw score, the
weight ``log(5)``, the max-skew adjustment, the integer quotient, an
empty cluster with ``max == 0``, ignored nodes, a hostname constraint);
what it refuses; a hand-worked two-round auction in which the second
round must count the first round's admits; the program's gang cycle
against the reference on seeded three-zone toys with unequal zones, with
no matching pod bound, and under ``maxSkew 1``; the row's control
(``no-soft-spread``) seen to fail; the herd a round makes and what the
reference's serial loop reads beside it; the hard row's ``precision``
line held by this reference on the hard row's records; the row's file
and entries; the count of ``kernels/spread_soft.py`` and the three
readers; the toy through a whole traced run.  A file of its own: a PR
that adds a row adds files to the benchmark and edits none."""

import dataclasses
import glob
import inspect
import json
import math
import os
from types import SimpleNamespace
from unittest import mock

import numpy as np
import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.kernels import auction, peaks, spread, spread_soft
from perfbench.lib import check, drive, spec, world
from perfbench.reference import topology_spread as hard_ref
from perfbench.reference import topology_spread_soft as ref
from perfbench.tools import cell_controls
from perfbench.tools import control as control_tool
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
ROW, CELL = "sp-prefspread-5000", "sp-prefspread-5000.saturated"
HARD_ROW = "sp-topologyspread-5000"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
             "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated"]
TEMPLATE = "pod-with-preferred-topology-spreading"
BLUE = (("color", "blue"),)
SOFT, HARD = "ScheduleAnyway", "DoNotSchedule"
CONTROL = "no-soft-spread"
PRECISION_CONTROL = "bf16-scores"
# name -> (unit, better, source, layer)
PR42 = {
    "soft_spread_constraints_per_cycle.sat":
        ("count", "lower", "program_counter", "prepare"),
    "soft_spread_zone_skew_per_cycle.sat":
        ("count", "lower", "program_counter", "device programs"),
    "auction_softspread_roofline":
        ("%", "higher", "device_trace", "device programs"),
}
# accepted metrics of other rows that find something to read in this cell
ALSO_READ = ("capacity_deferred_per_cycle.sat", "pod_axis_rows.sat",
             "pod_axis_live_pct.sat", "cluster_device_mb.sat",
             "delta_apply_device_ms_per_cycle.sat", "delta_apply_roofline",
             "delta_pods_walked_per_cycle.sat",
             "snapshot_pods_copied_per_cycle.sat")
MI = 1 << 20


def _control(name=CONTROL):
    return spec._load_module(
        os.path.join(REPO, "perfbench", "controls", name + ".py"),
        "toy42_" + name.replace("-", "_"))


# ------------------------------------------------- the reference, by hand

def _nodes(n, zones=("a", "b", "c"), bare=()):
    """``n`` nodes of upstream's shape; node ``i`` in ``zones[i % len]``;
    the nodes in ``bare`` carry no zone label."""
    out = []
    for i in range(n):
        labels = {HOSTNAME: f"node-{i}"}
        if i not in bare:
            labels[ZONE] = zones[i % len(zones)]
        out.append(world.NodeRec(f"node-{i}", 4000, 32 << 30, 110, labels))
    return out


@dataclasses.dataclass(frozen=True)
class Pod(world.PodRec):
    namespace: str = "default"
    terminating: bool = False


def _pod(name, labels=None, ns="default", terminating=False, **terms):
    return Pod(name, 100, 500 << 20, 0, dict(labels or {}), namespace=ns,
               terminating=terminating, **terms)


def _blue(name, **kw):
    return _pod(name, {"color": "blue"}, **kw)


def _soft(max_skew=5, key=ZONE, sel=BLUE, when=SOFT):
    return (max_skew, key, when, sel)


def _cluster(nodes, *bound):
    c = ref.Cluster(nodes)
    for pod, i in bound:
        c.add(pod, f"node-{i}")
    return c


def _fill(prefix, per_node, **kw):
    """Blue pods: ``per_node[i]`` of them on node i."""
    return [(_blue(f"{prefix}{z}-{i}", **kw), z)
            for z, n in enumerate(per_node) for i in range(n)]


LOG5 = 1.6094379124341003
# (what, nodes, bound, incoming pod, filtered rows or None = all,
#  raw int64 per node, normalised score per node)
SCORES = {
    # int64(7 x log 5) = 11, int64(6 x log 5) = 9, int64(12 x log 5) = 19;
    # 100 x (19 + 9 - s) / 19 in integers: 89 (89.47), 100, 47 (47.37)
    "the raw score, the weight log(5) and the integer quotient": (
        _nodes(3), _fill("p", (7, 6, 12)), _blue("in", spread=(_soft(1),)),
        None, [11, 9, 19], [89, 100, 47]),
    # a zone's count is over all its nodes, and each of them reads it
    "a pair's count is read on every node of the pair": (
        _nodes(6), _fill("p", (3, 6, 5, 4, 0, 7)),
        _blue("in", spread=(_soft(1),)), None,
        [11, 9, 19, 11, 9, 19], [89, 100, 47, 89, 100, 47]),
    # counts under maxSkew read maxSkew - 1 = 4: int64(4 x log 5) = 6
    "the max-skew adjustment": (
        _nodes(3), _fill("p", (0, 3, 8)), _blue("in", spread=(_soft(5),)),
        None, [6, 6, 12], [100, 100, 50]),
    "a count of exactly maxSkew stands": (
        _nodes(3), _fill("p", (5, 4, 9)), _blue("in", spread=(_soft(5),)),
        None, [8, 6, 14], [85, 100, 42]),
    "an empty cluster under maxSkew 1: max == 0, every node MaxNodeScore": (
        _nodes(3), [], _blue("in", spread=(_soft(1),)),
        None, [0, 0, 0], [100, 100, 100]),
    "an empty cluster under maxSkew 5: the zones tie above 0": (
        _nodes(3), [], _blue("in", spread=(_soft(5),)),
        None, [6, 6, 6], [100, 100, 100]),
    # a filtered node without the key is ignored: 0, out of min and max,
    # and out of the size; its pods are not counted anywhere
    "a node without the key is ignored": (
        _nodes(4, bare=(3,)), _fill("p", (7, 6, 12, 30)),
        _blue("in", spread=(_soft(1),)), None,
        [11, 9, 19, 0], [89, 100, 47, 0]),
    # zone c's only node is not filtered: its pair is not registered, the
    # size is 2 and the weight log(4); its pods are not read
    "a zone with no filtered node is not registered": (
        _nodes(3), _fill("p", (7, 6, 12)), _blue("in", spread=(_soft(1),)),
        [0, 1], [int(7 * math.log(4)), int(6 * math.log(4)), 0],
        [88, 100, 0]),
    # a pod on a node that is NOT filtered still counts for its zone
    "pods of unfiltered nodes count for a registered pair": (
        _nodes(6), _fill("p", (3, 6, 5, 4, 0, 7)),
        _blue("in", spread=(_soft(1),)), [0, 1, 2],
        [11, 9, 19, 0, 0, 0], [89, 100, 47, 0, 0, 0]),
    "a pod of another namespace is not counted": (
        _nodes(3), _fill("p", (7, 6, 12)) + _fill("q", (0, 9, 0),
                                                  ns="other"),
        _blue("in", spread=(_soft(1),)), None, [11, 9, 19], [89, 100, 47]),
    "a terminating pod is not counted": (
        _nodes(3), _fill("p", (7, 6, 12)) + _fill("q", (0, 9, 0),
                                                  terminating=True),
        _blue("in", spread=(_soft(1),)), None, [11, 9, 19], [89, 100, 47]),
    "a pod the selector misses is not counted": (
        _nodes(3), _fill("p", (7, 6, 12))
        + [(_pod(f"r{i}", {"color": "red"}), 1) for i in range(9)],
        _blue("in", spread=(_soft(1),)), None, [11, 9, 19], [89, 100, 47]),
    # the hostname key: size = the filtered nodes (4: log 6), the node's
    # own count: int64(c x 1.79) = 5, 0, 16, 1
    "a hostname constraint weighs by the scored nodes": (
        _nodes(4), _fill("p", (3, 0, 9, 1)),
        _blue("in", spread=(_soft(1, key=HOSTNAME),)), None,
        [5, 0, 16, 1], [68, 100, 0, 93]),
    # zone (log 5) and hostname (log 5 over three nodes) summed before the
    # truncation: 7 x log 5 + 7 x log 5 = 22.53 -> 22, not 11 + 11
    "two constraints are summed in float64, then truncated": (
        _nodes(3), _fill("p", (7, 6, 12)),
        _blue("in", spread=(_soft(1), _soft(1, key=HOSTNAME))), None,
        [22, 19, 38], [92, 100, 50]),
    "a pod without a soft constraint scores MaxNodeScore everywhere": (
        _nodes(3), _fill("p", (7, 6, 12)), _blue("in"), None,
        [0, 0, 0], [100, 100, 100]),
}


@pytest.mark.parametrize("what", sorted(SCORES))
def test_the_references_score_by_hand(what):
    nodes, bound, pod, rows, raw, score = SCORES[what]
    cluster = _cluster(nodes, *bound)
    filtered = np.zeros(len(nodes), bool)
    filtered[list(range(len(nodes))) if rows is None else rows] = True
    assert cluster.spread_raw(pod, filtered).tolist() == raw, what
    got = cluster.spread_score(pod, filtered)
    assert got.tolist() == score, what
    assert got.dtype == np.int64


def test_the_arithmetic_is_upstreams_float64_and_int64():
    assert ref.normalizing_weight(3) == math.log(5.0) == LOG5
    assert ref.SPREAD_WEIGHT == 2 and ref.MAX_NODE_SCORE == 100
    assert ref.adjust_for_max_skew(np.array([0, 4, 5, 6]), 5).tolist() \
        == [4, 4, 5, 6]
    # where a float32 product lands on the other side of the integer
    cluster = _cluster(_nodes(3))
    cluster.selected[("default", BLUE)] = np.array([4217, 0, 1], np.int64)
    raw = cluster.spread_raw(_blue("in", spread=(_soft(1),)),
                             np.ones(3, bool))
    assert raw.tolist() == [int(4217 * LOG5), 0, 1] == [6786, 0, 1]
    assert int(np.float32(4217) * np.log(np.float32(5.0))) == 6787


def test_the_weighted_sum_adds_twice_the_normalised_score():
    nodes, bound, pod, _, _, score = SCORES[
        "the raw score, the weight log(5) and the integer quotient"]
    cluster = _cluster(nodes, *bound)
    plain = _blue("in")
    diff = cluster.scores(pod) - cluster.scores(plain)
    assert diff.tolist() == [2 * s - 200 for s in score]
    # an infeasible node takes no part: fill zone b's node
    full = world.PodRec("full", 3350, 0, 0, {})
    cluster.add(full, "node-1")
    assert cluster.feasible(pod).tolist() == [True, False, True]
    assert cluster.tie_set(pod).tolist() == [0]
    # the zones that are left: size 2, weight log 4: 9 and 16
    assert cluster.spread_score(pod, cluster.fits(pod)).tolist() \
        == [100, 0, 100 * 9 // 16]


REFUSED = {
    "a DoNotSchedule constraint": dict(spread=(_soft(when=HARD),)),
    "a node-affinity term": dict(node_affinity_in=((ZONE, ("a",)),)),
    "a required anti-affinity term": dict(anti_required=((HOSTNAME, BLUE),)),
    "a required affinity term": dict(aff_required=((ZONE, BLUE),)),
    "a preferred anti-affinity term":
        dict(anti_preferred=((1, HOSTNAME, BLUE),)),
    "a preferred affinity term": dict(aff_preferred=((1, HOSTNAME, BLUE),)),
    "two ScheduleAnyway constraints on one topology key":
        dict(spread=(_soft(1), _soft(2, sel=(("color", "red"),)))),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_the_reference_refuses_what_it_does_not_model(what):
    pod = _blue("odd", **REFUSED[what])
    cluster = _cluster(_nodes(3))
    for call in (lambda: cluster.add(pod, "node-0"),
                 lambda: cluster.scores(pod),
                 lambda: cluster.feasible(pod),
                 lambda: cluster.terms_ok(pod),
                 lambda: ref.replay(_nodes(3), [(pod, "node-0")], {}, [], {})):
        with pytest.raises(NotImplementedError, match=what.split()[1]):
            call()


def test_it_imports_nothing_of_the_program():
    import ast
    with open(ref.__file__) as f:
        tree = ast.parse(f.read())
    names = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    names |= {n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)}
    assert not [n for n in names if n.split(".")[0] in ("kubetpu", "jax")]
    assert ref.gang_misses is ref._base.gang_misses


def test_the_replay_is_default_plugins_over_the_bare_records():
    """Check (a): the resource and bind lines, and no spread line."""
    nodes = [world.NodeRec("node-0", 250, 32 << 30, 110,
                           {HOSTNAME: "node-0", ZONE: "a"})]
    pods = {f"m-{i}": _blue(f"m-{i}", spread=(_soft(5),)) for i in range(4)}
    log = [("add", f"m-{i}", 0.0) for i in range(3)] \
        + [("bind", "m-0", "node-0", 1.0), ("bind", "m-1", "node-0", 1.1)]
    ok = ref.replay(nodes, [], pods, log, {"m-0": "node-0",
                                           "m-1": "node-0"})
    assert ok == []
    over = ref.replay(nodes, [], pods,
                      log + [("bind", "m-2", "node-0", 1.2)],
                      {f"m-{i}": "node-0" for i in range(3)})
    assert len(over) == 1 and "over allocatable cpu" in over[0]
    # a pod given up on that fits nowhere is no violation; one that fits is
    assert ref.replay(nodes, [], pods, log, {"m-0": "node-0",
                                             "m-1": "node-0"},
                      stuck=["m-3"]) == []
    roomy = [dataclasses.replace(nodes[0], cpu_milli=4000)]
    left = ref.replay(roomy, [], pods, log, {"m-0": "node-0",
                                             "m-1": "node-0"},
                      stuck=["m-3"])
    assert len(left) == 1 and "left unschedulable" in left[0]


# ----------------------------------- two rounds, worked out by hand

def _hand_nodes():
    """Zone a: node-0 (room for five pods in all) and node-1; zone b:
    node-2.  2,000m / 2,000Mi: a pod of q milli and q Mi loads cpu and
    memory alike, so BalancedAllocation reads 100 everywhere and
    LeastAllocated reads 100 - (used + q) / 20 exactly."""
    return [world.NodeRec(f"node-{i}", 2000, 2000 * MI, pods,
                          {HOSTNAME: f"node-{i}", ZONE: zone})
            for i, (zone, pods) in enumerate((("a", 5), ("a", 110),
                                              ("b", 110)))]


def _hand_blue(name, max_skew=1, constrained=True):
    return world.PodRec(name, 100, 100 * MI, 0, {"color": "blue"},
                        spread=(_soft(max_skew),) if constrained else ())


def _hand_world():
    """node-0 holds two blue pods, node-1 one plain pod of 1,000m, node-2
    five blue pods; the batch: five blue pods of 100m under maxSkew 1."""
    bound = ([(_hand_blue(f"x-{i}", constrained=False), "node-0")
              for i in range(2)]
             + [(world.PodRec("filler", 1000, 1000 * MI, 0, {}), "node-1")]
             + [(_hand_blue(f"y-{i}", constrained=False), "node-2")
                for i in range(5)])
    return bound, [_hand_blue(f"p{i}") for i in range(5)]


# round 1: zone a holds 2 blue pods, zone b 5: the weight is log(4), the
# raw scores int64(2.77) = 2 and int64(6.93) = 6, normalised
# 100 x (6 + 2 - s) / 6 = 100 and 33, twice that 200 and 66.  Zone a wins
# on both its nodes; LeastAllocated makes it node-0 (100 - 300/20 = 85
# against node-1's 100 - 1100/20 = 45).  node-0 has room for three more
# pods: p0..p2 are admitted, p3 and p4 find it full.
# round 2: node-0 is infeasible.  Zone a now holds 2 + 3 = 5 blue pods
# (node-0's count, though node-0 is no longer scored) and zone b 5: raw 6
# and 6, both 100, and LeastAllocated sends p3 and p4 to node-2 (70
# against 45).  With round 1's admits NOT counted zone a would still
# read 2: 200 against 66, and node-1 would win by 245 to 136.
FULL = dict(p0="node-0", p1="node-0", p2="node-0", p3="node-2", p4="node-2")
STALE = dict(FULL, p3="node-1", p4="node-1")


def _hand_cell(batch=16, mesh_shape=None):
    return SimpleNamespace(
        name="hand.closed", traffic={"resident_bound": 0},
        config={"scheduler": {"mode": "gang", "batch_size": batch},
                "mesh_shape": mesh_shape},
        reference=lambda: ref)


def _hand_cycle(nodes, bound, sample, cell=None):
    """One gang cycle of the program over a hand-made cluster, with the
    cycle's record: (placements, record meta)."""
    from kubetpu.utils import trace as utrace
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        placed = check.program_gang_cycle(cell or _hand_cell(), 42, nodes,
                                          bound, sample)
        cycles = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    ran = [c["meta"] for c in cycles if c["meta"].get("auction_rounds")]
    assert len(ran) == 1, cycles
    return placed, ran[0]


def _judge(nodes, bound):
    cluster = ref.Cluster(nodes)
    for rec, node in bound:
        cluster.add(rec, node)
    return cluster


def test_a_second_round_counts_the_first_rounds_admits():
    bound, sample = _hand_world()
    placed, meta = _hand_cycle(_hand_nodes(), bound, sample)
    assert placed == FULL
    assert ref.gang_misses(_judge(_hand_nodes(), bound), sample, placed) == []
    # two rounds that admit and, at this width, the empty one that ends it
    assert meta["auction_rounds"] == 3 and meta["needs_topo"] == 1
    assert meta["capacity_deferred"] == 2
    assert meta["spread_soft_constraints"] == 5
    assert meta["spread_constraints"] == 0
    assert meta["term_sets_live"] == ["spread_soft"]
    # after the cycle zone a holds 5 blue pods and zone b 7
    assert meta["spread_soft_skew"] == 2
    # the reference's own auction ends in the same places
    assert ref.auction_schedule(_judge(_hand_nodes(), bound), sample,
                                np.random.default_rng(0)) == FULL


def test_the_reference_flags_the_stale_count():
    """What a program that scored every round on the counts of the
    cycle's start would do: p3 and p4 on node-1, outside every round's
    tie set."""
    bound, sample = _hand_world()
    misses = ref.gang_misses(_judge(_hand_nodes(), bound), sample, STALE)
    assert len(misses) == 2
    assert misses[0].startswith("p3: node-1 outside")
    assert "score" in misses[0]


def test_the_control_moves_a_hand_worked_batch():
    """Zone b's node holds no blue pod and is the fullest of the three:
    the score sends the batch there, LeastAllocated alone (the control)
    sends it anywhere else, outside every round's tie set."""
    sample = _hand_world()[1]
    bound = ([(_hand_blue(f"x-{i}", constrained=False), "node-0")
              for i in range(4)]
             + [(world.PodRec("filler", 1500, 1500 * MI, 0, {}), "node-2")])
    nodes = _hand_nodes()
    placed, _ = _hand_cycle(nodes, bound, sample)
    assert set(placed.values()) == {"node-2"}
    assert ref.gang_misses(_judge(nodes, bound), sample, placed) == []
    with _control().program_control():
        moved, _ = _hand_cycle(nodes, bound, sample)
    assert "node-2" not in set(moved.values())
    assert len(ref.gang_misses(_judge(nodes, bound), sample, moved)) == 5


@pytest.mark.parametrize("mesh_shape", [(2, 4), (1, 8)])
def test_the_mesh_path_places_the_hand_worked_batch_alike(mesh_shape):
    bound, sample = _hand_world()
    placed, meta = _hand_cycle(_hand_nodes(), bound, sample,
                               _hand_cell(mesh_shape=mesh_shape))
    assert placed == FULL and meta["spread_soft_skew"] == 2


def test_an_empty_cluster_scores_every_node_alike():
    """No blue pod bound, maxSkew 1: every raw score is 0, ``max == 0``,
    every node MaxNodeScore; the resource plugins decide, every node
    ties, and one round places the batch anywhere it fits."""
    nodes = _nodes(6)
    sample = [_hand_blue(f"p{i}") for i in range(8)]
    placed, meta = _hand_cycle(nodes, [], sample)
    assert all(placed.values())
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []
    assert meta["spread_soft_constraints"] == 8
    # the same under the row's maxSkew 5 (the zones tie at int64(4 log 5))
    sample = [_hand_blue(f"q{i}", max_skew=5) for i in range(8)]
    placed, _ = _hand_cycle(nodes, [], sample)
    assert ref.gang_misses(_judge(nodes, []), sample, placed) == []


# ---------------------------------- seeded toy worlds of the template

def _toy_ref(batch):
    """The reference with its cycle length at the toy's batch size, so
    that check (b)'s residents are herded as the row's are."""
    ns = SimpleNamespace(**{k: getattr(ref, k) for k in dir(ref)
                            if not k.startswith("__")})

    def auction_schedule(cluster, pods, rng, **kw):
        with mock.patch.object(ref, "BURST", batch):
            return ref.auction_schedule(cluster, pods, rng, **kw)
    ns.auction_schedule = auction_schedule
    return ns


def toy_cell(nodes=48, batch=64, resident_bound=32, blue=20, max_skew=5,
             mesh_shape=None):
    """The row in small: three zones, nodes of 1,000m that hold ten pods
    (so a round's tie set fills and the rest go on: two or more rounds),
    one plain init pod a node and ``blue`` more that carry the label and
    no constraint, dealt by the seed: the zones start unequal."""
    row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                      ROW + ".json"))
    measured = dict(row["templates"][TEMPLATE])
    measured["topology_spread"] = [dict(measured["topology_spread"][0],
                                        max_skew=max_skew)]
    templates = dict(row["templates"], **{
        TEMPLATE: measured,
        "blue-plain": dict(row["templates"]["pod-default"],
                           labels={"color": "blue"})})
    config = dict(
        row, name="toy-prefspread-48", templates=templates,
        cluster=dict(row["cluster"], nodes=nodes, node={
            "cpu_milli": 1000, "memory_bytes": 34359738368, "pods": 110}),
        init_pods=[{"count": nodes, "template": "pod-default"}]
        + ([{"count": blue, "template": "blue-plain"}] if blue else []),
        scheduler={"mode": "gang", "batch_size": batch},
        mesh_shape=mesh_shape)
    world.validate(config)
    toy_ref = _toy_ref(batch)
    return SimpleNamespace(
        name="toy-prefspread-48.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: toy_ref, control=_control)


SEEDS = (42, 2 ** 31 + 42, 3500000942)


def _gang_check_with_rounds(cell, seed):
    from kubetpu.utils import trace as utrace
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        misses = check.gang_check(cell, seed, nodes, init)
        metas = [c.to_dict()["meta"] for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    return misses, [m for m in metas if m.get("auction_rounds")]


@pytest.mark.parametrize("seed", SEEDS)
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(seed):
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    # the world is what it is for: unequal zones before the sample
    cluster, bound = check.check_cluster(cell, cell.reference(), seed,
                                         nodes, init)
    probe = check.sample_records(cell, seed)[0]
    assert ref.zone_skew(cluster, probe) >= 16
    assert control_tool.reference_misses(cell, seed, nodes, init) == 0
    misses, ran = _gang_check_with_rounds(cell, seed)
    assert misses == []
    # several rounds: the tie set's nodes fill and the rest go on
    assert len(ran) == 1 and ran[0]["auction_rounds"] >= 3
    assert ran[0]["capacity_deferred"] > 0
    assert ran[0]["spread_soft_constraints"] == 64
    assert ran[0]["spread_soft_skew"] >= 0


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_same_with_no_matching_pod_bound(seed):
    """A cold start: plain init pods alone, no resident, the batch the
    first blue pods the cluster sees."""
    cell = toy_cell(blue=0, resident_bound=0)
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    sample = check.sample_records(cell, seed)
    placed = check.program_gang_cycle(cell, seed, nodes, init, sample)
    assert all(placed.values())
    assert ref.gang_misses(_judge(nodes, init), sample, placed) == []


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_same_under_max_skew_1(seed):
    """No count is watered down: every blue pod moves the score."""
    cell = toy_cell(max_skew=1)
    misses, ran = _gang_check_with_rounds(cell, seed)
    assert misses == [] and ran[0]["auction_rounds"] >= 2


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_skew_counter_is_the_references_count_after_the_cycle(seed):
    """``spread_soft_skew`` is one constraint's counts by pair id after
    the LAST round's admits (no product over the pod axis): the
    reference's own zone counts over the check cluster and the cycle's
    placements, several rounds deep."""
    from kubetpu.utils import trace as utrace
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    cluster, bound = check.check_cluster(cell, cell.reference(), seed,
                                         nodes, init)
    sample = check.sample_records(cell, seed)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        placed = check.program_gang_cycle(cell, seed, nodes, bound, sample)
        ran = [m for m in (c.to_dict()["meta"] for c in flight.cycles())
               if m.get("auction_rounds")]
    finally:
        utrace.disarm_flight_recorder()
    assert len(ran) == 1 and ran[0]["auction_rounds"] >= 3
    for rec in sample:
        if placed[rec.name]:
            cluster.add(rec, placed[rec.name])
    assert ran[0]["spread_soft_skew"] == ref.zone_skew(cluster, sample[0])


@pytest.mark.parametrize("seed", SEEDS[:1])
def test_the_mesh_path_places_the_toy_world_alike(seed):
    nodes = world.node_records(toy_cell().config)
    placed = {}
    for shape in (None, (2, 4)):
        cell = toy_cell(mesh_shape=shape)
        init = world.init_records(cell.config, seed)
        _, bound = check.check_cluster(cell, cell.reference(), seed, nodes,
                                       init)
        placed[shape] = check.program_gang_cycle(
            cell, seed, nodes, bound, check.sample_records(cell, seed))
    assert placed[None] == placed[(2, 4)]
    assert all(placed[None].values())


def test_the_rows_control_fails_on_every_seed():
    """``no-soft-spread`` in the program's place, and in the reference's:
    the emptiest nodes lie in every zone, the least zone's tie set holds
    a third of them."""
    cell = toy_cell()
    assert cell.config["control"] == CONTROL
    assert _control().REFERENCE_KW == {"no_soft_spread": True}
    nodes = world.node_records(cell.config)
    broken, by_reference = [], []
    for seed in SEEDS:
        init = world.init_records(cell.config, seed)
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, no_soft_spread=True))
        with cell.control().program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)


def test_the_sums_lower_precision_fails_the_toy_too():
    """``bf16-scores`` is the row's ``precision_control``: the weighted
    sum held in bfloat16 (steps of 4,096 near 1,000,593) ties every
    feasible node, and check (b) says so, in the reference's place and
    in the program's."""
    cell = toy_cell()
    bf16 = _control(PRECISION_CONTROL)
    assert bf16.REFERENCE_KW == {"lowprec": True}
    nodes = world.node_records(cell.config)
    broken, by_reference = [], []
    for seed in SEEDS[:2]:
        init = world.init_records(cell.config, seed)
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, **bf16.REFERENCE_KW))
        with bf16.program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)


def test_the_float32_product_is_a_lower_precision_no_check_b_can_see():
    """``f32-product`` puts the parent's raw score back.  It IS another
    number: at 4,217 matching pods a zone, three zones, the host's
    float32 product floors to 6,787 where float64 (6,786.9997) and
    ``log_weighted_floor`` have 6,786.  And check (b) cannot see it, on
    the toy or on the row: the quotient it moves belongs to a zone that
    is not the least (the row's ``precision`` says so)."""
    import jax.numpy as jnp
    from kubetpu.ops import kernels
    f32 = _control("f32-product")
    assert f32.REFERENCE_KW == {"f32_product": True}
    cnt = jnp.full((1, 1, 1), 4217.0, jnp.float32)
    size = jnp.full((1, 1), 3.0, jnp.float32)
    on = jnp.ones((1, 1, 1), bool)
    exact = int(kernels.log_weighted_floor(cnt, size, on, 8)[0, 0])
    assert exact == int(4217 * math.log(5.0)) == 6786
    real = kernels.log_weighted_floor
    with f32.program_control():
        assert kernels.log_weighted_floor is not real
        assert int(kernels.log_weighted_floor(cnt, size, on, 8)[0, 0]) \
            == exact + 1
    assert kernels.log_weighted_floor is real
    # the reference's switch is the same arithmetic
    nodes = _nodes(3)
    cluster = ref.Cluster(nodes)
    assert ref.normalizing_weight(3) == math.log(5.0)
    assert ref.normalizing_weight(3, np.float32) == np.log(np.float32(5))
    assert isinstance(ref.normalizing_weight(3, np.float32), np.float32)
    assert cluster.product_dtype is np.float64
    # and the toy's check (b) reads 0 under it, both halves
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    seed = SEEDS[0]
    init = world.init_records(cell.config, seed)
    assert control_tool.reference_misses(cell, seed, nodes, init,
                                         **f32.REFERENCE_KW) == 0
    with f32.program_control():
        assert check.gang_check(cell, seed, nodes, init) == []


def test_the_controls_tool_reads_the_row_at_its_own_size(capsys):
    """``tools/cell_controls.py`` on the ROW, the reference's halves (no
    jax, a second a seed): the row's own two controls fail, the float32
    product does not, which is what ``precision`` states."""
    assert cell_controls.main(
        ["--workload", CELL, "--seeds", "4200000101", "--program", "0",
         "--controls", "f32-product,no-soft-spread"]) == 0
    line = [l for l in capsys.readouterr().out.splitlines()
            if l.startswith("CONTROLS ")]
    assert len(line) == 1
    row = json.loads(line[0][len("CONTROLS "):])
    assert list(row)[:4] == ["workload", "seed", "batch", "reference"]
    assert list(row)[4:] == ["reference:" + CONTROL,
                             "reference:" + PRECISION_CONTROL,
                             "reference:f32-product"]
    assert row["reference"] == 0 and row["reference:f32-product"] == 0
    assert row["reference:" + CONTROL] >= 100
    assert row["reference:" + PRECISION_CONTROL] >= 100


def test_on_level_zones_the_check_cannot_tell_a_working_score_from_none():
    """Why the reference places check (b)'s residents a cycle at a time:
    ONE auction over all of them from an empty cluster leaves the zones
    level (every count under maxSkew ties), and then the greedy
    explanation admits a third of the control's placements a round, each
    round's admits making another zone the least: the control reads 0."""
    cell = toy_cell(nodes=96, batch=32, resident_bound=32, blue=0)
    level = SimpleNamespace(config=cell.config, traffic=cell.traffic,
                            reference=lambda: ref)     # burst 1,024: one
    nodes = world.node_records(cell.config)
    seed = SEEDS[0]
    init = world.init_records(cell.config, seed)
    probe = check.sample_records(cell, seed)[0]
    skews = {}
    for name, c in (("level", level), ("herded", cell)):
        cluster, _ = check.check_cluster(c, c.reference(), seed, nodes, init)
        skews[name] = ref.zone_skew(cluster, probe)
    assert skews["level"] <= 12 < 24 <= skews["herded"]
    assert control_tool.reference_misses(level, seed, nodes, init,
                                         no_soft_spread=True) == 0
    assert control_tool.reference_misses(cell, seed, nodes, init,
                                         no_soft_spread=True) >= 1


def test_the_herd_and_the_serial_loop():
    """A round is admitted on the round's first scores: the gang cycle
    sends its whole batch to the zone that was least (as far as its nodes
    have room), a serial scheduler re-scores after every pod.  The
    numbers PERF.md, Open questions, cites."""
    cell = toy_cell(nodes=96, batch=64, resident_bound=0, blue=0)
    nodes = world.node_records(cell.config)
    seed = SEEDS[0]
    init = world.init_records(cell.config, seed)
    sample = check.sample_records(cell, seed)
    skew = {}
    for name, schedule in (("gang", ref.auction_schedule),
                           ("serial", ref.serial_schedule)):
        cluster = _judge(nodes, init)
        # three cycles of a batch, as a window would bind them: the first
        # ties everywhere (counts under maxSkew), the second goes to the
        # least zone whole, the third to the next
        for k in range(3):
            schedule(cluster, [dataclasses.replace(r, name=f"{r.name}-{k}")
                               for r in sample], np.random.default_rng(k))
        skew[name] = ref.zone_skew(cluster, sample[0])
    assert skew["serial"] <= 5 < 32 <= skew["gang"], skew


# ------------------------------ the hard row's precision line, held

def test_the_soft_score_is_constant_on_the_hard_rows_records():
    """``sp-topologyspread-5000.json``'s ``precision`` says v1.19's
    PodTopologySpread scores only ScheduleAnyway constraints, so the
    score is the same on every node there.  This reference, which DOES
    score, agrees on the hard row's own records: a measured pod of that
    row (one DoNotSchedule constraint, no ScheduleAnyway one) reads
    MaxNodeScore on every filtered node whatever the zones hold, so the
    two references cannot drift apart."""
    hard = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       HARD_ROW + ".json"))
    assert "scores only ScheduleAnyway constraints" in hard["precision"]
    toy = dict(hard, cluster=dict(hard["cluster"], nodes=30),
               init_pods={"template": "pod-default", "count": 30})
    world.validate(toy)
    nodes = world.node_records(toy)
    measured = world.measured_record(toy, "measured", 0)
    assert [c[2] for c in measured.spread] == [HARD]
    assert ref.soft_constraints(measured) == ()
    cluster = ref.Cluster(nodes)
    for rec, node in world.init_records(toy, 42):
        cluster.add(rec, node)
    # blue pods, very unequal by zone (the hard row's own residents,
    # which this reference takes without their DoNotSchedule constraint)
    for i in range(40):
        blue = dataclasses.replace(
            world.measured_record(toy, "resident", i), spread=())
        cluster.add(blue, nodes[(i % 13) * 3 % 30 if i % 4 else 0].name)
    for filtered in (np.ones(30, bool), np.arange(30) % 2 == 0):
        got = cluster.spread_score(measured, filtered)
        assert got[filtered].tolist() == [100] * int(filtered.sum())
        assert not got[~filtered].any()
        assert not cluster.spread_raw(measured, filtered).any()
    # the same pod with the one word changed IS scored, on the same state
    soft = dataclasses.replace(measured, spread=tuple(
        (s, k, SOFT, sel) for s, k, _, sel in measured.spread))
    assert len(set(cluster.spread_score(soft, np.ones(30, bool)))) > 1
    # and over the same bound pods the hard reference's scores for its
    # pod are this reference's less the constant 2 x MaxNodeScore
    judge = hard_ref.Cluster(nodes)
    for pod, r in cluster.bound.values():
        judge.add(pod, nodes[r].name)
    plain = dataclasses.replace(measured, spread=())
    assert (judge.scores(measured) + 200 == cluster.scores(plain)).all()


# ------------------------------------------------- the file, the entries

@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later42")), "checkout"))


@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_is_the_hard_row_with_one_word_changed(row):
    world.validate(row)
    hard = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       HARD_ROW + ".json"))
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["mesh_shape"] is None and "warmup" not in row
    for key in ("cluster", "init_pods", "scheduler"):
        assert row[key] == hard[key], key
    assert row["measured_pods"] == {"template": TEMPLATE}
    assert row["templates"]["pod-default"] \
        == hard["templates"]["pod-default"]
    theirs = hard["templates"]["pod-with-topology-spreading"]
    mine = row["templates"][TEMPLATE]
    assert theirs["topology_spread"][0]["when_unsatisfiable"] == HARD
    assert mine["topology_spread"][0]["when_unsatisfiable"] == SOFT
    swap = json.loads(json.dumps(theirs).replace(HARD, SOFT))
    assert mine == swap
    assert mine == perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]
    assert row["reference"] == "topology_spread_soft"
    assert row["control"] == CONTROL
    assert len(row["source"]) <= 200
    for word in ("performance-config.yaml", "PreferredTopologySpreading",
                 "5000Nodes", "pod-default.yaml",
                 "pod-with-preferred-topology-spreading.yaml",
                 "node-default.yaml"):
        assert word in row["source"], word
    assert row["guarantees"][:3] == hard["guarantees"][:3]
    assert "no pod left unschedulable" in row["guarantees"][3]
    assert "PodTopologySpread's normalised score (weight 2)" \
        in row["guarantees"][4]
    # it states NO skew bound, and says so
    assert not any("bound where the skew" in g for g in row["guarantees"])
    assert any(g.startswith("NO skew bound") for g in row["guarantees"])
    for key in ("batch_size", "mode", "init_pods", "measured_pods",
                "departures", "namespace", "templates", "zone",
                "scoring_snapshot"):
        assert key in row["assumed"], key
    assert "unverified" in row["assumed"]["templates"]
    assert "scoreForCount" in row["assumed"]["scoring_snapshot"]
    for word in ("float64", "16,384", "8,192", "4,217",
                 "tests/test_spread_soft_product.py",
                 # it says what check (b) holds and what it cannot
                 "WHAT THE CELL'S CHECK (b) HOLDS", "bf16-scores",
                 "WHAT IT DOES NOT HOLD", "f32-product", "ALONE"):
        assert word in row["precision"], word
    assert row["precision_control"] == PRECISION_CONTROL
    for name in (CONTROL, PRECISION_CONTROL, "f32-product"):
        assert os.path.exists(os.path.join(
            REPO, "perfbench", "controls", name + ".py")), name
    assert "PLACEHOLDER" not in json.dumps(row)
    rec = world.measured_record(row, "measured", 7)
    assert rec.labels == {"color": "blue"}
    assert rec.spread == ((5, ZONE, SOFT, BLUE),)
    assert not (rec.aff_required or rec.anti_required or rec.aff_preferred
                or rec.anti_preferred or rec.node_affinity_in)
    init = world.pod_record(row, "pod-default", "init", 0)
    assert init.labels == {} and init.spread == ()
    zones = [n.labels[ZONE] for n in world.node_records(row)]
    assert [zones.count(z) for z in ("moon-1", "moon-2", "moon-3")] \
        == [1667, 1667, 1666]


def test_the_references_cycle_length_is_every_such_rows_batch_size():
    """How check (b)'s cluster is populated is the reference's constant,
    not a parameter: it has to be the batch size of EVERY row this
    reference judges, or a row's check cluster is one no window of it
    starts a cycle from (``lib/check.py`` hands the reference no batch
    size: PERF.md, Open questions)."""
    rows = [spec.load_json(path) for path in sorted(glob.glob(
        os.path.join(REPO, "perfbench", "configs", "*.json")))]
    mine = [r for r in rows if r.get("reference") == "topology_spread_soft"]
    assert [r["name"] for r in mine] == [ROW]
    for r in mine:
        assert ref.BURST == r["scheduler"]["batch_size"], r["name"]
    assert "burst" not in inspect.signature(ref.auction_schedule).parameters


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_row_and_its_three_metrics(later,
                                                            later_root):
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[57:60] == list(PR42)
    if later:
        assert names[60:]
    for name, (unit, better, source, layer) in PR42.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][0] == CELL
    assert [w["name"] for w in bench["workloads"]][:7] == OLD_CELLS + [CELL]
    entry = bench["configs"][6]
    assert entry["name"] == ROW and entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{ROW}.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = spec.cell(CELL, root)
    assert cell.config["source"] == entry["source"]
    assert cell.chips == 1 and cell.entry["traffic"] == "saturated-d4096"
    assert len(cell.entry["why"]) <= 200
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]
    # it reads every metric all six older cells read, its own three, and
    # the eight of other rows whose layer this cell runs too (the pod
    # axis, the delta scatter, the cluster's bytes, capacity's
    # deferrals); nothing that has nothing to read here
    for m in bench["per_layer"][:57]:
        listed = m.get("workloads", [])
        if listed[:6] == OLD_CELLS or m["name"] in ALSO_READ:
            assert CELL in listed, m["name"]
        else:
            assert CELL not in listed, m["name"]
    for name in ("auction_rounds_per_cycle.sat",
                 "auction_admits_per_round.sat",
                 "auction_term_sets_live_per_cycle.sat",
                 "auction_device_ms_per_cycle.sat", "pods_bound_per_s"):
        assert CELL in (by_name.get(name) or next(
            m for m in bench["end_to_end"] if m["name"] == name))[
                "workloads"]
    assert set(cell.readers()) >= set(PR42)
    assert cell.reference().__name__.endswith("topology_spread_soft")
    assert cell.control().REFERENCE_KW == {"no_soft_spread": True}


# --------------------------------------------------- the count, by hand

def test_soft_spread_ops_against_a_hand_count():
    # 4 pods x 1 constraint x (10 countable pods x (compare + and +
    # namespace) + its own add); one round: 4 pods x (6 nodes x 3 + 3
    # pairs + the log) + 4 x 6 x 5 to truncate and normalise
    assert spread_soft.ops(4, 6, 1, 10, 1.0, 1.0, 3.0) \
        == 4 * (10 * 3 + 1) + 4 * (6 * 3 + 3 + 1) + 4 * 6 * 5
    # three rounds: at least 4 + 3 pods proposing
    assert spread_soft.ops(4, 6, 3, 10, 1.0, 1.0, 3.0) \
        == 4 * (10 * 3 + 1) + 7 * (6 * 3 + 3 + 1) + 7 * 6 * 5
    # two labels a selector: five operations a pair
    assert spread_soft.ops(4, 6, 1, 10, 1.0, 2.0, 3.0) \
        == 4 * (10 * 5 + 1) + 4 * (6 * 3 + 3 + 1) + 4 * 6 * 5
    # no soft constraint: nothing, whatever else is said
    assert spread_soft.ops(4, 6, 3, 10, 0.0) == 0.0
    assert spread_soft.bytes_moved(4, 6, 3, 10, 0.0) == 0.0
    assert spread_soft.bytes_moved(4, 6, 2, 10, 1.0, 3.0, 1.0) \
        == 4 * (3 * 10 + 5 * 4 + 6 + 2 * 3 * 2)
    assert spread.pod_rounds(4, 3) == 7


def test_the_rows_shapes_come_from_its_file_alone(row):
    assert spread_soft.shapes_of(row, world) == {
        "constraints_per_pod": 1.0, "labels_per_selector": 1.0,
        "keys": 1.0, "pairs": 3.0}
    # the hard row's constraint is the other kind, on either side
    hard = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       HARD_ROW + ".json"))
    assert spread_soft.shapes_of(hard, world)["constraints_per_pod"] == 0.0
    assert spread.shapes_of(row, world)["constraints_per_pod"] == 0.0
    for other in ("sp-basic-5000", "sp-antiaffinity-5000", "sp-mixed-5000",
                  "sigscale-150k", "sp-prefaffinity-5000"):
        cfg = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                          other + ".json"))
        assert spread_soft.shapes_of(cfg, world)[
            "constraints_per_pod"] == 0.0
    pk = peaks.peak("TPU v5 lite")
    least = spread_soft.least_seconds(
        1024, 5000, 2.0, pk.flops_per_s, pk.bytes_per_s, 6024,
        **spread_soft.shapes_of(row, world))
    plain = auction.least_seconds(1024, 5000, 2.0, pk.flops_per_s,
                                  pk.bytes_per_s)
    want = (1024 * (7048 * 3 + 1) + 1025 * (5000 * 3 + 3 + 1)
            + 1025 * 5000 * 5)
    assert least["soft_spread_ops"] == want
    assert least["ops_seconds"] == pytest.approx(
        plain["ops_seconds"] + want / pk.flops_per_s, rel=1e-12)
    assert least["bound"] == "operations"


def _cycle42(t, says=True, rounds=1, soft=1024, skew=1000):
    c = base._cycle(t)
    c["meta"] = {"auction_rounds": rounds, "pods": 1024}
    if says:
        c["meta"].update(spread_soft_constraints=soft)
        if soft:
            c["meta"].update(spread_soft_skew=skew)
    return c


def _ctx42(cycles, trace=None, of=CELL):
    cell = spec.cell(of, REPO)
    return cell, SimpleNamespace(
        cycles=cycles, cell=cell, trace=trace or {"modules": {}},
        device={"platform": "tpu", "kind": "TPU v5 lite"}, n_nodes=5000,
        resident_pods=6024)


TRACE = {"modules": {"jit__schedule_gang(7)": {"count": 2,
                                               "seconds": 0.02}}}


def test_the_three_readers_by_hand(row):
    two = [_cycle42(0.0), _cycle42(1.0, rounds=2, soft=1000, skew=400)]
    cell, ctx = _ctx42(two, TRACE)
    readers = cell.readers()
    assert readers["soft_spread_constraints_per_cycle.sat"](ctx) == 1012.0
    assert readers["soft_spread_zone_skew_per_cycle.sat"](ctx) == 700.0
    pk = peaks.peak("TPU v5 lite")
    least = spread_soft.least_seconds(
        1024, 5000, 1.5, pk.flops_per_s, pk.bytes_per_s, 6024,
        **spread_soft.shapes_of(row, world))
    share = readers["auction_softspread_roofline"](ctx)
    assert share == pytest.approx(100.0 * least["seconds"] / 0.01,
                                  rel=1e-12)
    assert 0 < share < 100.0
    # a cycle whose batch carried no soft constraint says 0 rows and no
    # skew: the skew is the mean over the cycles that have one
    mixed = two + [_cycle42(2.0, soft=0)]
    cell, ctx = _ctx42(mixed, TRACE)
    assert readers["soft_spread_zone_skew_per_cycle.sat"](ctx) == 700.0
    assert readers["soft_spread_constraints_per_cycle.sat"](ctx) \
        == pytest.approx(2024 / 3)


@pytest.mark.parametrize("name", sorted(PR42))
def test_a_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent says neither counter; the share reads any program that
    ran the auction, from the configuration and the round count."""
    parent = [_cycle42(0.0, says=False), _cycle42(1.0, says=False)]
    cell, ctx = _ctx42(parent, TRACE)
    got = cell.readers()[name](ctx)
    if name == "auction_softspread_roofline":
        assert got is not None and got > 0
    else:
        assert got is None
        cell, ctx = _ctx42([_cycle42(0.0)] + parent[:1], TRACE)
        assert cell.readers()[name](ctx) is None
    for cycles in ([], [base._cycle(0.0)]):
        cell, ctx = _ctx42(cycles)
        assert cell.readers()[name](ctx) is None


def test_the_share_is_silent_for_a_row_without_a_soft_constraint():
    cell42 = spec.cell(CELL, REPO)
    for other in (OLD_CELLS[0], OLD_CELLS[3]):
        cell, ctx = _ctx42([_cycle42(0.0)], TRACE, of=other)
        assert cell42.readers()["auction_softspread_roofline"](ctx) is None


# ------------------------------------------------ the toy, a whole run

TOY = dict(
    perfbench_toy.TOY_BASIC, name="toy-prefspread-48",
    cluster={"nodes": 48, "node": {"cpu_milli": 1000,
                                   "memory_bytes": 34359738368,
                                   "pods": 110},
             "node_labels": {ZONE: ["moon-1", "moon-2", "moon-3"]}},
    init_pods={"count": 48, "template": "pod-default"},
    measured_pods={"template": TEMPLATE},
    templates={"pod-default": perfbench_toy.UPSTREAM_TEMPLATES["pod-default"],
               TEMPLATE: perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]},
    scheduler={"mode": "gang", "batch_size": 32},
    reference="topology_spread_soft", control=CONTROL,
    precision="as sp-prefspread-5000",
    guarantees=["as sp-prefspread-5000"])
TOY_CELL = "toy-prefspread-48.closed32"
TOY_TRAFFIC = dict(perfbench_toy.TOY_TRAFFIC, name="closed32", depth=64,
                   resident_bound=32,
                   warmup=dict(perfbench_toy.TOY_TRAFFIC["warmup"],
                               quiet_binds=128))
LISTED = set(PR42) | {"auction_rounds_per_cycle.sat",
                      "auction_admits_per_round.sat",
                      "auction_term_sets_live_per_cycle.sat"} | set(ALSO_READ)


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy42")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": TOY["name"], "source": TOY["source"],
        "file": f"perfbench/configs/{TOY['name']}.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": TOY["name"], "traffic": "closed32",
        "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "pods_bound_per_s" or m["name"] in LISTED:
            m["workloads"].append(TOY_CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    for sub, what in (("configs", TOY), ("traffic", TOY_TRAFFIC)):
        with open(os.path.join(root, "perfbench", sub,
                               what["name"] + ".json"), "w") as f:
            json.dump(what, f)
    return root


def _whole_run(root, seed, trace):
    from kubetpu.utils import sanitize
    cell = spec.cell(TOY_CELL, root)
    said, kept = [], {}

    def keep(**kw):
        kept.update(kw)
        return SimpleNamespace(**kw)
    armed = list(sanitize._watchdogs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(drive, "SimpleNamespace", keep)
        try:
            res = drive.run_cell(cell, seed=seed, seconds=3.0, trace=trace,
                                 require_tpu=False, out=said.append)
        finally:
            for wd in list(sanitize._watchdogs):
                if wd not in armed:
                    sanitize.uninstall_compile_watchdog(wd)
    return res, kept, "\n".join(said)


@pytest.fixture(scope="module")
def toy_traced(toy_root):
    return _whole_run(toy_root, SEEDS[0], True)


def test_a_traced_toy_run_is_correct_and_fills_the_counters(toy_traced):
    res, ctx, said = toy_traced
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # off the chip there is no device plane: the share says nothing
    assert set(got) & set(PR42) == set(PR42) - {"auction_softspread_roofline"}
    # the other rows' metrics it is listed for find something to read
    # (the two of the device's trace on the chip alone)
    assert set(got) & set(ALSO_READ) == set(ALSO_READ) - {
        "delta_apply_device_ms_per_cycle.sat", "delta_apply_roofline"}
    assert got["pod_axis_rows.sat"] >= 32 and got["cluster_device_mb.sat"] > 0
    # every pod of every batch carries the one constraint
    assert 1 <= got["soft_spread_constraints_per_cycle.sat"] <= 32
    assert got["auction_term_sets_live_per_cycle.sat"] == 1.0
    # a cycle sends its pods to the least zone: the zones are left a
    # good part of a batch apart
    assert got["soft_spread_zone_skew_per_cycle.sat"] >= 4


def test_every_cycle_of_the_toy_run_says_what_it_scored(toy_traced):
    res, ctx, said = toy_traced
    ran = [c["meta"] for c in ctx["cycles"]
           if c["meta"].get("auction_rounds")]
    assert ran
    for m in ran:
        assert m["term_sets_live"] == ["spread_soft"]
        assert m["needs_topo"] == 1 and m["spread_constraints"] == 0
        assert m["spread_soft_constraints"] == m["pods"]
        assert 0 <= m["spread_soft_skew"] <= 32 + 32 + m["pods"]


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_toy_is_correct_on_two_more_seeds(toy_root, toy_traced, seed):
    res, _, said = _whole_run(toy_root, seed, False)
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    assert res["metrics"]["pods_bound_per_s"]["value"] > 0
