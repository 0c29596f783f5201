"""``sp-topologyspread-5000`` (upstream's TopologySpreading row): its
reference (perfbench/reference/topology_spread.py) against hand-worked
cases of v1.19 ``podtopologyspread/filtering.go``; the program's gang
cycle held to that reference on seeded three-zone clusters, through a
fresh build and through the delta path with departures between the
cycles; the row's control (``blind-batch``) seen to fail in both places;
the spread line of the replay fed the two races it must not flag and the
fault it must; what the reference refuses; the configuration file."""

import dataclasses
import os
from types import SimpleNamespace

import numpy as np
import pytest

import perfbench_toy
import test_perfbench_mixed as mixed
from perfbench.lib import check, spec, world
from perfbench.reference import topology_spread as ref
from perfbench.tools import control as control_tool
from perfbench.tools import spread_slack

ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
RACK = "example.com/rack"
ROW = "sp-topologyspread-5000"
CELL = ROW + ".saturated"
BLUE = (("color", "blue"),)
HARD = "DoNotSchedule"


# ------------------------------------------------- the reference, by hand

def _nodes(n, zones=("a", "b", "c"), bare=(), racks=None):
    """``n`` nodes of upstream's shape; node ``i`` in ``zones[i % len]``;
    the nodes in ``bare`` carry no zone label; ``racks``: node row -> its
    rack label (the others carry none)."""
    out = []
    for i in range(n):
        labels = {HOSTNAME: f"node-{i}"}
        if i not in bare:
            labels[ZONE] = zones[i % len(zones)]
        if racks and i in racks:
            labels[RACK] = racks[i]
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


def _spread(max_skew=5, key=ZONE, sel=BLUE, when=HARD):
    return (max_skew, key, when, sel)


def _cluster(nodes, *bound):
    c = ref.Cluster(nodes)
    for pod, i in bound:
        c.add(pod, f"node-{i}")
    return c


def _fill(prefix, per_zone, **kw):
    """Blue pods on the first three nodes: ``per_zone[z]`` in zone z."""
    return [(_blue(f"{prefix}{z}-{i}", **kw), z)
            for z, n in enumerate(per_zone) for i in range(n)]


# (what, nodes, bound [(pod, node row)...], incoming pod, rows that pass)
FILTERS = {
    "a skew of exactly maxSkew passes": (
        _nodes(3), _fill("p", (4, 0, 0)),
        _blue("in", spread=(_spread(5),)), [0, 1, 2]),      # 4 + 1 - 0 = 5
    "a skew of maxSkew + 1 fails": (
        _nodes(3), _fill("p", (5, 0, 0)),
        _blue("in", spread=(_spread(5),)), [1, 2]),         # 5 + 1 - 0 = 6
    "the self match counts: a pod its own selector misses gets one more": (
        _nodes(3), _fill("p", (5, 0, 0)),
        _pod("in", {"color": "red"}, spread=(_spread(5),)), [0, 1, 2]),
    "the minimum is over registered zones only": (
        # zone c's nodes lack the second constraint's key: not eligible,
        # so c's pair is not registered and its 0 pods are not the minimum
        _nodes(6, racks={0: "r", 1: "r", 3: "r", 4: "r"}),
        _fill("p", (3, 2, 0)),
        _blue("in", spread=(_spread(1), _spread(9, key=RACK))),
        [1, 4]),             # min is b's 2: a 3 + 1 - 2 = 2 > 1; c: no rack
    "a node without the zone label is infeasible": (
        _nodes(4, bare=(3,)), [],
        _blue("in", spread=(_spread(5),)), [0, 1, 2]),
    "a pod of another namespace is not counted": (
        _nodes(3), _fill("p", (9, 0, 0), ns="other"),
        _blue("in", spread=(_spread(1),)), [0, 1, 2]),
    "a terminating pod is not counted": (
        _nodes(3), _fill("p", (9, 0, 0), terminating=True),
        _blue("in", spread=(_spread(1),)), [0, 1, 2]),
    "a pod the selector misses is not counted": (
        _nodes(3), [(_pod(f"r{i}", {"color": "red"}), 0) for i in range(9)],
        _blue("in", spread=(_spread(1),)), [0, 1, 2]),
    "no node carries the key: an empty state lets every node through": (
        _nodes(3, bare=(0, 1, 2)), [],
        _blue("in", spread=(_spread(1),)), [0, 1, 2]),
    "every constraint has to hold": (
        _nodes(6, racks={i: "r%d" % (i // 3) for i in range(6)}),
        _fill("p", (2, 0, 0)),       # rack r0 holds 2, r1 none
        _blue("in", spread=(_spread(5), _spread(1, key=RACK))),
        [3, 4, 5]),                  # r0: 2 + 1 - 0 = 3 > 1
}


@pytest.mark.parametrize("what", sorted(FILTERS))
def test_the_filter_against_hand_worked_cases_of_filtering_go(what):
    nodes, bound, incoming, want = FILTERS[what]
    c = _cluster(nodes, *bound)
    ok = c.terms_ok(incoming)
    assert np.flatnonzero(ok).tolist() == want
    assert [c.terms_ok(incoming, r) for r in range(len(nodes))] \
        == ok.tolist()


def test_a_delete_lowers_the_minimum_and_blocks_the_other_zones():
    nodes = _nodes(3)
    bound = _fill("p", (5, 5, 1))
    c = _cluster(nodes, *bound)
    incoming = _blue("in", spread=(_spread(5),))
    assert np.flatnonzero(c.terms_ok(incoming)).tolist() == [0, 1, 2]
    # zone c's one pod leaves: the minimum falls to 0 and a, b are shut
    c.remove(bound[-1][0])
    assert np.flatnonzero(c.terms_ok(incoming)).tolist() == [2]
    # ...and an admission into c opens them again, seen at once
    c.add(_blue("q"), "node-2")
    assert np.flatnonzero(c.terms_ok(incoming)).tolist() == [0, 1, 2]
    assert c.tie_set(incoming).tolist() == [2]     # the emptiest node


@pytest.mark.parametrize("field,value", [
    ("spread", (_spread(5, when="ScheduleAnyway"),)),
    ("node_affinity_in", ((ZONE, ("a",)),)),
    ("anti_required", ((HOSTNAME, BLUE),)),
    ("aff_preferred", ((1, HOSTNAME, BLUE),))])
def test_the_reference_refuses_what_it_does_not_model(field, value):
    c = ref.Cluster(_nodes(3))
    with pytest.raises(NotImplementedError) as e:
        c.add(_pod("x", **{field: value}), "node-0")
    assert field in str(e.value)
    with pytest.raises(NotImplementedError):
        c.terms_ok(_pod("y", **{field: value}))


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        text = f.read()
    assert "kubetpu" not in text.replace("kubetpu/", "")
    assert "import jax" not in text


# ------------------------------------------------------ the configuration

@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_states_upstreams_shapes_and_cuts_nothing(row):
    world.validate(row)
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["templates"] == {
        t: perfbench_toy.UPSTREAM_TEMPLATES[t]
        for t in ("pod-default", "pod-with-topology-spreading")}
    assert world.init_groups(row) == [("pod-default", 5000)]
    assert row["scheduler"] == {"mode": "gang", "batch_size": 1024}
    assert row["cluster"]["nodes"] == 5000
    assert row["cluster"]["node_labels"] == {
        ZONE: ["moon-1", "moon-2", "moon-3"]}
    assert "zones" not in row["cluster"]
    assert "unverified" in row["assumed"]["templates"]
    assert (row["reference"], row["control"]) == ("topology_spread",
                                                  "blind-batch")
    m = world.measured_record(row, "measured", 7)
    assert m.labels == {"color": "blue"}
    assert m.spread == ((5, ZONE, HARD, BLUE),)
    cell = spec.cell(CELL)
    assert cell.entry["traffic"] == "saturated-d4096" and cell.chips == 1
    bench = spec.load_benchmark()
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert not listed & {"auction_roofline", "auction_terms_roofline",
                         "term_rows_rebuilt_per_cycle.sat",
                         "terms_upload_ms_per_cycle.sat"}
    assert {"auction_rounds_per_cycle.sat", "auction_admits_per_round.sat",
            "spread_constraints_per_cycle.sat", "auction_spread_roofline",
            "auction_device_ms_per_cycle.sat"} <= listed
    # every .sat metric the three older cells all report
    assert listed >= {
        m["name"] for m in bench["per_layer"] if m["name"].endswith(".sat")
        and {"sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated"} <= set(m.get("workloads", []))}
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]


def test_the_rows_nodes_take_the_three_zones_in_turn(row):
    nodes = world.node_records(row)
    zones = [n.labels[ZONE] for n in nodes]
    assert zones[:4] == ["moon-1", "moon-2", "moon-3", "moon-1"]
    assert sorted(map(zones.count, set(zones))) == [1666, 1667, 1667]
    assert all(set(n.labels) == {HOSTNAME, ZONE} for n in nodes)


# -------------------------- the program held to the reference, in small

def toy_cell(nodes=120, batch=32, resident_bound=32, max_skew=5):
    """Upstream's row in small: one plain init pod a node, three zones in
    turn, the measured template as written (``max_skew`` other than 5 only
    where a test says why), this row's reference and control.  Few pods on
    many nodes, as in the row: every zone keeps nodes that hold one pod,
    so the emptiest node of a zone is what every round's tie set holds.
    (On a cluster as full as its batch the reference's own auction can
    end on a fuller node once a zone's emptiest are gone, in a round whose
    zones stood otherwise than any round of the greedy explanation: 7 and
    5 misses on two seeds of 40 at 30 nodes / 16 pods and 120 / 64, none
    at 60 / 16, 120 / 32, 240 / 64 or at the row's own size; CPU runs.)"""
    row = spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                      ROW + ".json"))
    templates = dict(row["templates"])
    spread = dict(templates["pod-with-topology-spreading"])
    spread["topology_spread"] = [dict(spread["topology_spread"][0],
                                      max_skew=max_skew)]
    templates["pod-with-topology-spreading"] = spread
    config = dict(
        row, name="toy-spread", cluster=dict(row["cluster"], nodes=nodes),
        init_pods={"template": "pod-default", "count": nodes},
        templates=templates,
        scheduler={"mode": "gang", "batch_size": batch})
    world.validate(config)
    module = spec._load_module(
        os.path.join(spec.ROOT, "perfbench", "controls", "blind-batch.py"),
        "toy_spread_blind_batch")
    return SimpleNamespace(
        name="toy-spread.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: ref, control=lambda: module)


SEEDS = (1, 2, 2 ** 31 + 7)
SIZES = {"60 nodes, batches of 16": dict(nodes=60, batch=16,
                                          resident_bound=16),
         "120 nodes, batches of 32": dict(nodes=120, batch=32,
                                           resident_bound=32)}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("size", sorted(SIZES))
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(
        size, seed):
    """A fresh build: check (b) as the harness runs it, the residents
    placed by the reference's own auction under the constraint."""
    cell = toy_cell(**SIZES[size])
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    assert control_tool.reference_misses(cell, seed, nodes, init) == 0
    assert check.gang_check(cell, seed, nodes, init) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_through_the_delta_path_with_departures(seed):
    """Three batches placed a cycle each, a third of the placed pods
    deleted after every cycle (each delete lowers a zone's count), then
    the sample's cycle on the tensors the delta path kept."""
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    cluster, bound = check.check_cluster(cell, ref, seed, nodes, init)
    churn = [[world.measured_record(cell.config, f"churn{k}", i)
              for i in range(32)] for k in range(3)]
    sample = check.sample_records(cell, seed)
    placed, left, records = mixed._churned_cycle(cell, seed, nodes, bound,
                                                 churn, sample)
    assert records[0]["meta"]["resync"] is False
    # every churn pod was placed, and the store's zones keep the skew
    assert all(node for _, node in left)
    for rec, node in left:
        cluster.add(rec, node)
    assert ref.gang_misses(cluster, sample, placed) == []


def test_the_rows_control_fails_in_the_program_and_in_the_reference():
    """``blind-batch``: with the batch's own pods left out of the count
    the pods fall on the zones at random and what exceeds the least
    zone's count + maxSkew lies outside every round's feasible set; the
    tree and the reference's own auction read 0.  The toy states maxSkew
    1: 32 pods thrown at three zones differ by 5 or less in most draws,
    where the row's 1,024 differ by tens (PERF.md has its readings with
    upstream's 5)."""
    cell = toy_cell(max_skew=1)
    control = cell.control()
    assert control.REFERENCE_KW == {"blind_batch": True}
    nodes = world.node_records(cell.config)
    sound, broken, reference, by_reference = [], [], [], []
    for seed in SEEDS:
        init = world.init_records(cell.config, seed)
        sound.append(len(check.gang_check(cell, seed, nodes, init)))
        reference.append(control_tool.reference_misses(cell, seed, nodes,
                                                       init))
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, **control.REFERENCE_KW))
        with control.program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
    assert sound == [0, 0, 0] and reference == [0, 0, 0]
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)


def test_a_blind_auction_leaves_the_clusters_counts_true():
    cell = toy_cell(nodes=30, batch=16, resident_bound=16)
    nodes = world.node_records(cell.config)
    cluster = ref.Cluster(nodes)
    pods = [world.measured_record(cell.config, "x", i) for i in range(40)]
    ref.auction_schedule(cluster, pods, np.random.default_rng(3),
                         blind_batch=True)
    assert cluster.blind_batch is False
    counted = int(cluster._selected("default", BLUE).sum())
    assert counted == len(cluster.bound) == 40


# ------------------------------------------- the replay's spread line

def _log_cluster(per_zone):
    """Nine nodes in three zones, ``per_zone`` blue residents a zone as
    init pods; returns (nodes, init, a name -> record map to add to)."""
    nodes = _nodes(9)
    init = [(_blue(f"init-{z}-{i}"), z)
            for z, n in enumerate(per_zone) for i in range(n)]
    return nodes, [(p, f"node-{r}") for p, r in init], {}


def _constrained(pods, name):
    pods[name] = _blue(name, spread=(_spread(5),))
    return name


def test_the_spread_line_flags_a_burst_poured_into_one_zone():
    """Twelve pods a cycle, all into zone a, cycle after cycle: the
    second cycle's binds stand on the first's, which no race can take
    away."""
    nodes, init, pods = _log_cluster((2, 2, 2))
    log = []
    for k in range(3):
        names = [_constrained(pods, f"m{k}-{i}") for i in range(12)]
        log += [("add", n, float(k)) for n in names]
        log += [("bind", n, "node-0", k + 0.5) for n in names]
    out = ref.spread_violations(nodes, init, pods, log, burst=12)
    assert out and all("topology spread violated" in v for v in out)
    # the first cycle's own binds excuse each other (the round order is
    # not known), so nothing of it is flagged; the later cycles are
    flagged = {v.split()[3].rstrip(":") for v in out}
    assert not any(n.startswith("m0-") for n in flagged)
    assert any(n.startswith("m1-") for n in flagged)
    assert any(n.startswith("m2-") for n in flagged)
    # through the whole replay too, which takes a cycle for the row's
    # batch size
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ref, "BURST", 12)
        assert ref.replay(nodes, init, pods, log,
                          {n: "node-0" for n in pods}) == out


def test_a_delete_logged_between_a_pods_add_and_its_bind_is_not_flagged():
    """Zones 7 / 7 / 3.  The pod is added, then the client deletes two of
    zone c's pods, then the bind into zone a arrives.  The cycle that
    decided it took its snapshot before those deletes: it saw 7 + 1 - 3 =
    5, legal; replayed strictly in log order the bind would read
    7 + 1 - 1 = 7."""
    nodes, init, pods = _log_cluster((7, 7, 3))
    name = _constrained(pods, "m")
    log = [("add", name, 0.0),
           ("delete", "init-2-0", 0.1), ("delete", "init-2-1", 0.2),
           ("bind", name, "node-0", 0.3)]
    assert ref.spread_violations(nodes, init, pods, log, burst=4) == []
    # the same deletes logged BEFORE the add were delivered before the
    # pod could be popped: every cycle that decided it had seen them,
    # and then the bind is a violation
    early = [log[1], log[2], log[0], log[3]]
    out = ref.spread_violations(nodes, init, pods, early, burst=4)
    assert len(out) == 1 and "m on node-0" in out[0]


def test_a_burst_in_batch_order_is_not_flagged_where_rounds_went_otherwise():
    """Zones 0 / 0 / 0, one cycle of 21 pods, seven a zone, decided round
    by round a / b / c; the lane binds them in batch order, which here
    is all of zone a first: replayed strictly in log order the seventh
    pod of a would stand at 6 + 1 - 0 = 7."""
    nodes, init, pods = _log_cluster((0, 0, 0))
    names = [_constrained(pods, f"m{i}") for i in range(21)]
    log = [("add", n, 0.0) for n in names]
    log += [("bind", n, f"node-{i // 7}", 1.0 + i / 100)
            for i, n in enumerate(names)]
    assert ref.spread_violations(nodes, init, pods, log, burst=21) == []
    # with the cycle's size understated the same log IS flagged: the
    # window of ``burst`` binds is what keeps the line sound
    assert ref.spread_violations(nodes, init, pods, log, burst=2)


def test_the_slack_of_every_constrained_bind_is_handed_back():
    nodes, init, pods = _log_cluster((4, 1, 1))
    name = _constrained(pods, "m")
    plain = "plain"
    pods[plain] = _pod(plain)
    log = [("add", name, 0.0), ("add", plain, 0.0),
           ("bind", plain, "node-0", 0.1), ("bind", name, "node-0", 0.2)]
    slack = []
    assert ref.spread_violations(nodes, init, pods, log, burst=1,
                                 slack=slack) == []
    assert slack == [(4, 1, 1, 5)]     # one constrained bind: 4 + 1 - 1


def test_the_slack_tool_sums_up_what_the_line_compared():
    rows = [(0, 1, 340, 5), (2, 1, 338, 5), (4, 1, 1, 5), (9, 1, 1, 5)]
    got = spread_slack.summary(rows)
    assert got["low_z"]["n"] == 4 and got["low_z"]["max"] == 9
    assert got["high_min"]["min"] == 1
    # room left: 5 - (low + self - high_min); the last bind is a violation
    assert got["room"]["min"] == 5 - (9 + 1 - 1) == -4
    assert got["room"]["max"] == 5 - (0 + 1 - 340)
    assert spread_slack.quartiles([7]) == {"n": 1}


def test_replay_keeps_default_plugins_other_lines():
    nodes, init, pods = _log_cluster((1, 1, 1))
    a, b = _constrained(pods, "a"), _constrained(pods, "b")
    log = [("add", a, 0.0), ("add", b, 0.0), ("bind", a, "node-1", 0.1),
           ("bind", a, "node-2", 0.2), ("bind", b, "node-77", 0.3)]
    out = ref.replay(nodes, init, pods, log, {"a": "node-0"}, stuck=["b"])
    assert out == ["pod a bound twice: node-1, node-2",
                   "pod b bound to unknown node node-77",
                   "read-back: a bound to node-1, store holds 'node-0', "
                   "expected 'node-1'",
                   "b left unschedulable; the reference can place it"]
