"""The plain reference flags every planted violation, passes a sound
schedule, and its controls fail check (b)."""

import numpy as np
import pytest

from perfbench.lib import world
from perfbench.reference import default_plugins as ref

CONFIG = {
    "cluster": {"nodes": 12, "zones": 3,
                "node": {"cpu_milli": 400, "memory_bytes": 4 << 30,
                         "pods": 3}},
    "init_pods": {"count": 6, "template": "anti"},
    "measured_pods": {"template": "anti"},
    "templates": {
        "anti": {"cpu_milli": 100, "memory_bytes": 256 << 20,
                 "group_labels": 4, "features": ["anti"]},
        "plain": {"cpu_milli": 100, "memory_bytes": 256 << 20,
                  "group_labels": 4, "features": []},
        "big": {"cpu_milli": 300, "memory_bytes": 256 << 20,
                "group_labels": 4, "features": []},
        "spready": {"cpu_milli": 100, "memory_bytes": 1 << 20,
                    "features": ["spread"]},
    },
}


def pod(template, role, i):
    return world.pod_record(CONFIG, template, role, i)


@pytest.fixture()
def nodes():
    return world.node_records(CONFIG)


def sound_log(nodes, pods, rng=None):
    """A schedule by the reference's own float64 auction, as a log."""
    cluster = ref.Cluster(nodes)
    placed = ref.auction_schedule(cluster, pods,
                                  rng or np.random.default_rng(0))
    log = []
    for p in pods:
        log.append(("add", p.name, 0.0))
        log.append(("bind", p.name, placed[p.name], 0.0))
    return log, placed


def test_a_sound_schedule_replays_clean(nodes):
    pods = [pod("anti", "measured", i) for i in range(20)]
    log, placed = sound_log(nodes, pods)
    assert all(placed.values())
    out = ref.replay(nodes, [], {p.name: p for p in pods}, log,
                     dict(placed))
    assert out == []
    # ...and a delete frees the room and the read-back expects it gone
    log.append(("delete", pods[0].name, 0.0))
    rb = dict(placed)
    rb[pods[0].name] = None
    assert ref.replay(nodes, [], {p.name: p for p in pods}, log, rb) == []


def test_planted_over_commit_is_flagged(nodes):
    pods = [pod("big", "measured", i) for i in range(2)]
    log = [("bind", p.name, "node-0", 0.0) for p in pods]
    out = ref.replay(nodes, [], {p.name: p for p in pods}, log,
                     {p.name: "node-0" for p in pods})
    assert any("over allocatable cpu" in v for v in out), out
    # pod count: four small pods on a node of three
    small = [pod("plain", "measured", i) for i in range(4)]
    log = [("bind", p.name, "node-1", 0.0) for p in small]
    out = ref.replay(nodes, [], {p.name: p for p in small}, log,
                     {p.name: "node-1" for p in small})
    assert any("over allocatable pods" in v for v in out), out


def test_planted_double_bind_and_unknown_node_are_flagged(nodes):
    p = pod("plain", "measured", 0)
    q = pod("plain", "measured", 1)
    log = [("bind", p.name, "node-0", 0.0), ("bind", p.name, "node-1", 0.0),
           ("bind", q.name, "node-99", 0.0), ("bind", "ghost", "node-0", 0)]
    out = ref.replay(nodes, [], {p.name: p, q.name: q}, log,
                     {p.name: "node-0"})
    assert any("bound twice" in v for v in out), out
    assert any("unknown node" in v for v in out), out
    assert any("never offered" in v for v in out), out


def test_planted_anti_affinity_violation_is_flagged(nodes):
    init = [(pod("anti", "init", 0), "node-2")]
    p = pod("anti", "measured", 4)       # same app group as init-0
    assert p.labels["app"] == init[0][0].labels["app"]
    out = ref.replay(nodes, init, {p.name: p},
                     [("bind", p.name, "node-2", 0.0)], {p.name: "node-2"})
    assert any("affinity violated" in v for v in out), out
    # symmetric: a PLAIN pod of the group lands beside a pod whose own
    # term forbids it
    q = pod("plain", "measured", 8)
    out = ref.replay(nodes, init, {q.name: q},
                     [("bind", q.name, "node-2", 0.0)], {q.name: "node-2"})
    assert any("affinity violated" in v for v in out), out
    # another node is fine
    assert ref.replay(nodes, init, {p.name: p},
                      [("bind", p.name, "node-3", 0.0)],
                      {p.name: "node-3"}) == []


def test_read_back_mismatch_and_placeable_stuck_pod_are_flagged(nodes):
    p = pod("plain", "measured", 0)
    q = pod("plain", "measured", 1)
    out = ref.replay(nodes, [], {p.name: p, q.name: q},
                     [("bind", p.name, "node-0", 0.0)],
                     {p.name: "node-5"}, stuck=[q.name])
    assert any("read-back" in v for v in out), out
    assert any("left unschedulable" in v for v in out), out


def test_off_tie_set_placement_is_flagged(nodes):
    pods = [pod("plain", "sample", i) for i in range(6)]

    def start():
        c = ref.Cluster(nodes)
        c.add(pod("big", "resident", 0), "node-11")
        return c
    placed = ref.auction_schedule(start(), pods, np.random.default_rng(1))
    assert "node-11" not in placed.values()
    assert ref.gang_misses(start(), pods, placed) == []
    # one pod moved onto the node that holds the big pod: feasible, not
    # best in any round
    bad = dict(placed)
    bad[pods[-1].name] = "node-11"
    misses = ref.gang_misses(start(), pods, bad)
    assert len(misses) == 1 and "outside every round's tie set" in misses[0]
    assert "score" in misses[0]
    # left pending though a node has room
    bad = dict(placed)
    bad[pods[2].name] = ""
    assert any("left pending" in m
               for m in ref.gang_misses(ref.Cluster(nodes), pods, bad))
    # and a node nobody knows
    bad = dict(placed)
    bad[pods[0].name] = "node-x"
    assert any("unknown node" in m
               for m in ref.gang_misses(ref.Cluster(nodes), pods, bad))


def test_pods_of_one_round_may_share_a_node_and_later_rounds_see_them(
        nodes):
    """The auction's own semantics: every proposal of a round is judged
    against the round's START, so two pods may take the same best node;
    what does not fit or breaks a term waits for the next round."""
    pods = [pod("plain", "sample", i) for i in range(3)]
    same = {p.name: "node-3" for p in pods}
    assert ref.gang_misses(ref.Cluster(nodes), pods, same) == []
    # a node holds three pods: a fourth proposal waits for the next
    # round, where the full node is in no tie set
    many = [pod("plain", "sample", i) for i in range(4)]
    misses = ref.gang_misses(ref.Cluster(nodes), many,
                             {p.name: "node-3" for p in many})
    assert len(misses) == 1 and "sample-3" in misses[0]
    # two pods of one anti-affinity group on one node: the second is
    # never explained
    a, b = pod("anti", "sample", 0), pod("anti", "sample", 4)
    assert a.labels["app"] == b.labels["app"]
    misses = ref.gang_misses(ref.Cluster(nodes), [a, b],
                             {a.name: "node-1", b.name: "node-1"})
    assert len(misses) == 1 and "infeasible" in misses[0]
    # a later round is judged against what the earlier ones placed: with
    # node-0 full after round one, node-1 (one pod) is round two's best
    # only because node-0 is out
    start = ref.Cluster(nodes)
    for i in range(2, 12):
        for j in range(2):
            start.add(pod("plain", "resident", 10 * i + j), f"node-{i}")
    start.add(pod("plain", "resident", 1), "node-1")
    four = {p.name: "node-0" for p in many[:3]}
    four[many[3].name] = "node-1"
    assert ref.gang_misses(start, many, four) == []


def test_scores_follow_upstream_integer_arithmetic(nodes):
    cluster = ref.Cluster(nodes)
    p = pod("plain", "measured", 0)
    # empty 400m / 4Gi node, pod 100m / 256Mi:
    #   least: cpu (400-100)*100/400 = 75, mem (4096-256)*100/4096 = 93
    #          -> (75 + 93) / 2 = 84
    #   balanced: |0.25 - 0.0625| = 0.1875 -> int(81.25) = 81
    s = cluster.scores(p)
    assert s[0] == 84 + 81 + ref.CONSTANT_SCORE
    cluster.add(p, "node-0")
    s = cluster.scores(pod("plain", "measured", 1))
    #   least: cpu 50, mem 87 -> 68 ; balanced: |0.5 - 0.125| -> 62
    assert s[0] == 68 + 62 + ref.CONSTANT_SCORE
    assert s[1] == 84 + 81 + ref.CONSTANT_SCORE


def _big_nodes(n):
    cfg = dict(CONFIG, cluster={"nodes": n, "zones": 4,
                                "node": {"cpu_milli": 4000,
                                         "memory_bytes": 32 << 30,
                                         "pods": 110}})
    return cfg, world.node_records(cfg)


def test_low_precision_control_fails_the_gang_check():
    """bfloat16 cannot hold 1,000,000 + 165: the weighted sum collapses,
    every feasible node ties, and the control lands off the tie set."""
    cfg, nodes = _big_nodes(200)
    base = [world.pod_record(cfg, "plain", "resident", i)
            for i in range(120)]
    sample = [world.pod_record(cfg, "plain", "sample", i)
              for i in range(64)]

    def start():
        c = ref.Cluster(nodes)
        ref.auction_schedule(c, base, np.random.default_rng(5))
        return c
    sound = ref.auction_schedule(start(), sample, np.random.default_rng(6))
    assert ref.gang_misses(start(), sample, sound) == []
    control = ref.auction_schedule(start(), sample,
                                   np.random.default_rng(6), lowprec=True)
    assert len(ref.gang_misses(start(), sample, control)) >= 5


def test_blind_batch_control_fails_the_gang_check():
    """Every pod excludes every other (upstream's anti-affinity row): an
    auction that does not look at its own batch puts two on one node."""
    cfg, nodes = _big_nodes(100)
    cfg["templates"] = {"all": {"cpu_milli": 100, "memory_bytes": 1 << 20,
                                "group_labels": 1, "features": ["anti"]}}
    sample = [world.pod_record(cfg, "all", "sample", i) for i in range(48)]
    sound = ref.auction_schedule(ref.Cluster(nodes), sample,
                                 np.random.default_rng(2))
    assert len(set(sound.values())) == 48
    assert ref.gang_misses(ref.Cluster(nodes), sample, sound) == []
    control = ref.auction_schedule(ref.Cluster(nodes), sample,
                                   np.random.default_rng(2),
                                   blind_batch=True)
    assert len(set(control.values())) < 48
    assert len(ref.gang_misses(ref.Cluster(nodes), sample, control)) >= 3


def test_bf16_rounds_to_eight_bits():
    assert float(ref.bf16(1000200.0)) == 999424.0
    assert float(ref.bf16(257.0)) == 256.0
    assert float(ref.bf16(96.0)) == 96.0


def test_features_outside_the_reference_raise(nodes):
    p = pod("spready", "measured", 0)
    with pytest.raises(NotImplementedError):
        ref.Cluster(nodes).feasible(p)


# what a record may HOLD that this reference does not model, whatever the
# template called it (literal terms here; "spready" above is the shorthand)
_T = {"topology_key": world.HOSTNAME, "match_labels": {"color": "red"}}
UNMODELLED = {
    "a preferred affinity term": {"pod_affinity": [dict(_T, weight=1)]},
    "a preferred anti-affinity term": {
        "pod_anti_affinity": [dict(_T, weight=1)]},
    "a topology spread constraint": {"topology_spread": [dict(
        _T, max_skew=5, when_unsatisfiable="ScheduleAnyway")]},
    "a node-affinity term": {"node_affinity_in": {
        "key": world.ZONE, "values": ["zone-0"]}},
    "a required term on two labels": {"pod_anti_affinity": [{
        "topology_key": world.HOSTNAME, "required": True,
        "match_labels": {"color": "red", "app": "x"}}]},
}


@pytest.mark.parametrize("existing", [False, True],
                         ids=["incoming", "existing"])
@pytest.mark.parametrize("what", sorted(UNMODELLED))
def test_a_record_that_holds_what_the_reference_does_not_model_raises(
        nodes, what, existing):
    config = dict(CONFIG, templates=dict(CONFIG["templates"], odd=dict(
        {"cpu_milli": 100, "memory_bytes": 1 << 20,
         "labels": {"color": "red"}}, **UNMODELLED[what])))
    odd = world.pod_record(config, "odd", "measured", 0)
    plain = pod("plain", "measured", 1)
    cluster = ref.Cluster(nodes)
    with pytest.raises(NotImplementedError) as e:
        if existing:
            cluster.add(odd, "node-0")      # the cluster already holds it
        else:
            cluster.feasible(odd)
    assert odd.name in str(e.value)
    # ...and through the entries the check calls
    if existing:
        with pytest.raises(NotImplementedError):
            ref.replay(nodes, [(odd, "node-0")], {plain.name: plain},
                       [("bind", plain.name, "node-1", 0.0)],
                       {plain.name: "node-1"})
    else:
        with pytest.raises(NotImplementedError):
            ref.auction_schedule(cluster, [plain, odd],
                                 np.random.default_rng(0))
        with pytest.raises(NotImplementedError):
            ref.gang_misses(ref.Cluster(nodes), [odd], {odd.name: "node-0"})


@pytest.mark.parametrize("name", ["sp-basic-5000", "sp-antiaffinity-5000"])
def test_the_reference_accepts_every_record_of_the_two_rows(name):
    import os
    from perfbench.lib import spec
    config = spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                         name + ".json"))
    cluster = ref.Cluster(world.node_records(config))
    init = world.init_records(config, seed=3)
    for rec, node in init:
        cluster.add(rec, node)              # existing
    for role in ("measured", "resident", "sample"):
        rec = world.measured_record(config, role, 17)
        assert cluster.feasible(rec).any()  # incoming
    # a literal required term on one label is what it models, too
    green = world.pod_record({"templates": {"g": {
        "cpu_milli": 100, "memory_bytes": 1 << 20,
        "labels": {"color": "green"}, "pod_anti_affinity": [{
            "topology_key": world.HOSTNAME, "required": True,
            "match_labels": {"color": "green"}}]}}}, "g", "measured", 0)
    cluster.add(green, "node-0")
    assert not cluster.feasible(green)[0] and cluster.feasible(green)[1]
