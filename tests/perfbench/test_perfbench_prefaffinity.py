"""The row PR 40 added, ``sp-prefaffinity-5000`` (upstream's
SchedulingPreferredPodAffinity: every pod prefers its peers' node), and
what it forced in the program: the batch's own score-side terms counted
inside the auction (``models/gang.py`` ``_extend_cluster``).

A hand-worked two-round auction in which the half count (2c + k: a pod
admitted earlier in the auction counted by the later pod's term, not by
its own) and the full count (2c + 2k, upstream's serial loop and the
reference) normalise to different integers and the resource plugins flip
the argmax; the same for preferred ANTI-affinity and for required
affinity at ``hardPodAffinityWeight``; the program's gang cycle against
``interpod_terms`` on seeded toy worlds of this template whose nodes
hold ten pods, fresh build, delta path and mesh; the row's file and
entries; the count of ``kernels/preferred_terms.py`` and the three
readers; the toy through a whole traced run; the controls.  A file of
its own: a PR that adds a row adds files to the benchmark and edits
none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_mixed as mixed
import test_perfbench_spans as base
from perfbench.kernels import auction, existing_terms, peaks, preferred_terms
from perfbench.lib import check, drive, spec, world
from perfbench.reference import batch_blind_terms as blind
from perfbench.reference import interpod_terms as ref
from perfbench.tools import batch_terms_control, control as control_tool
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
HOSTNAME = world.HOSTNAME
ROW, CELL = "sp-prefaffinity-5000", "sp-prefaffinity-5000.saturated"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
             "sigscale-150k.saturated"]
TEMPLATE = "pod-with-preferred-pod-affinity"
RED = (("color", "red"),)
# name -> (unit, better, source)
PR40 = {
    "score_terms_spliced_per_cycle.sat": ("count", "lower", "program_span"),
    "capacity_deferred_per_cycle.sat": ("count", "lower", "program_span"),
    "auction_prefscore_roofline": ("%", "higher", "device_trace"),
}
NO_SPLICE = "no-batch-score-terms"


def _control(name):
    return spec._load_module(
        os.path.join(REPO, "perfbench", "controls", name + ".py"),
        "toy40_" + name.replace("-", "_"))


# ------------------------------------- two rounds, worked out by hand

MI = 1 << 20


def _hand_nodes():
    """Three nodes of 2,000m / 2,000Mi: a pod of q milli and q Mi loads
    cpu and memory alike, so BalancedAllocation reads 100 everywhere and
    LeastAllocated reads 100 - (used + q) / 20 exactly."""
    return [world.NodeRec(f"node-{i}", 2000, 2000 * MI, 110,
                          {HOSTNAME: f"node-{i}"}) for i in range(3)]


def _red(name, q=100, **terms):
    return world.PodRec(name, q, q * MI, 0, {"color": "red"}, **terms)


def _hand_cell(batch=16, scheduler=None, mesh_shape=None):
    return SimpleNamespace(
        name="hand.closed", traffic={"resident_bound": 0},
        config={"scheduler": dict({"mode": "gang", "batch_size": batch},
                                  **(scheduler or {})),
                "mesh_shape": mesh_shape},
        reference=lambda: ref)


def _hand_cycle(bound, sample, cell=None):
    """One gang cycle of the program over the hand-made cluster, with the
    cycle's record: (placements, record meta)."""
    from kubetpu.utils import trace as utrace
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=16, max_spans_per_cycle=64)
    try:
        placed = check.program_gang_cycle(cell or _hand_cell(), 40,
                                          _hand_nodes(), bound, sample)
        cycles = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    ran = [c["meta"] for c in cycles if c["meta"].get("auction_rounds")]
    assert len(ran) == 1, cycles
    return placed, ran[0]


def _judge(bound):
    cluster = ref.Cluster(_hand_nodes())
    for rec, node in bound:
        cluster.add(rec, node)
    return cluster


def _affinity_world(term):
    """node-0 holds nine red pods, node-1 eight, node-2 none; every pod,
    bound or pending, carries ``term``.  The batch, in queue order: seven
    of 100m, one of 500m, one of 100m."""
    bound = ([(_red(f"x-{i}", **term), "node-0") for i in range(9)]
             + [(_red(f"y-{i}", **term), "node-1") for i in range(8)])
    sample = ([_red(f"p{i}", **term) for i in range(7)]
              + [_red("p7", q=500, **term), _red("p8", **term)])
    return bound, sample


PREFERRED = {"aff_preferred": ((1, HOSTNAME, RED),)}
# round 1, every pod: node-0 has the most red pods (raw 18 against 16
# and 0: 100, 88, 0) and wins over LeastAllocated's 5 points a pod
# (small pod: 50 + 100 against 55 + 88 and 95 + 0).  node-0 has 1,100m
# free: p0..p6 take 700, p7's 500 does not fit behind them, and p8
# behind p7 is refused by the same prefix (700 + 500 + 100).
# round 2: node-0 holds 16.  p7 no longer fits there and goes to node-1.
# p8 sees, with the seven admitted pods' own terms counted (upstream,
# the reference, the program since PR 40): raw 32 against 16, so 100
# against 50, and 15 + 100 = 115 on node-0 beats 55 + 50 = 105 on
# node-1.  With only p8's own term counting them (the parent): raw
# 9 + 16 = 25 against 16, so 100 against 64, and 55 + 64 = 119 on node-1
# beats 115.
FULL = dict({f"p{i}": "node-0" for i in range(7)}, p7="node-1", p8="node-0")
HALF = dict(FULL, p8="node-1")


def test_the_batchs_own_preferred_terms_count_inside_the_auction():
    bound, sample = _affinity_world(PREFERRED)
    placed, meta = _hand_cycle(bound, sample)
    assert placed == FULL
    assert ref.gang_misses(_judge(bound), sample, placed) == []
    # two rounds that admit and, at this width (no residual window), the
    # empty round that ends the loop
    assert meta["auction_rounds"] == 3 and meta["needs_topo"] == 1
    # p7 and p8 found node-0 full at their turn in round 1, nobody after
    assert meta["capacity_deferred"] == 2
    assert meta["score_terms_spliced"] == 9
    assert "pref" in meta["term_sets_live"]
    # the reference's own auction admits p8 in round 1 (it fits behind
    # p0..p6 once p7 is refused) and ends in the same places
    import numpy as np
    assert ref.auction_schedule(_judge(bound), sample,
                                np.random.default_rng(0)) == FULL


def test_the_half_count_is_what_the_splice_cured():
    """The control patches the splice off: the parent's program.  Its
    p8 lands on node-1, outside every round's tie set."""
    bound, sample = _affinity_world(PREFERRED)
    with _control(NO_SPLICE).program_control():
        placed, meta = _hand_cycle(bound, sample)
    assert placed == HALF
    assert meta["score_terms_spliced"] == 0
    misses = ref.gang_misses(_judge(bound), sample, placed)
    assert len(misses) == 1 and misses[0].startswith("p8: node-1 outside")
    # and the reference's counterpart reads the half count too
    import numpy as np
    cluster = blind.Cluster(_hand_nodes())
    for rec, node in bound:
        cluster.add(rec, node)
    got = blind.auction_schedule(cluster, sample, np.random.default_rng(0),
                                 no_batch_score_terms=True)
    # (its admission lets p8 in behind p0..p6 in round 1, so the half
    # count never gets to decide p8; it decides nothing here)
    assert got == FULL


def test_required_affinity_terms_are_spliced_at_the_hard_weight():
    """Every pod REQUIRES a red pod on its node (node-2 is infeasible)
    and scores by the existing pods' required terms alone, 1 an owner:
    raw 9 against 8 in round 1; in round 2 16 against 8 with the admitted
    pods' terms (100 against 50: node-0 by 115 to 105), 9 against 8
    without (100 against 88: node-1 by 143 to 115)."""
    term = {"aff_required": ((HOSTNAME, RED),)}
    bound, sample = _affinity_world(term)
    placed, meta = _hand_cycle(bound, sample)
    assert placed == FULL and meta["score_terms_spliced"] == 9
    assert meta["term_sets_live"] == ["ra"]
    assert ref.gang_misses(_judge(bound), sample, placed) == []
    with _control(NO_SPLICE).program_control():
        assert _hand_cycle(bound, sample)[0] == HALF


def test_preferred_anti_affinity_terms_are_spliced_with_their_sign():
    """node-0 holds four red pods, node-1 eight; every pod prefers NOT to
    share a node with a red pod (weight 1).  Round 1: raw -8 against -16,
    50 against 0, everyone proposes node-0 (1,600m free): six of 100m
    fit, the pod of 1,100m does not, the last of 100m is refused behind
    it.  Round 2, node-0 holding ten: the big pod fits node-1 alone.  The
    last pod sees raw -20 against -16 with the admitted pods' terms
    counted: 0 on node-0, 20 on node-1, and node-1 wins by 55 + 20 to
    45 + 0.  Counted by its own term alone they read -14 against -16:
    12 on node-0, 0 on node-1, and node-0 would win by 57 to 55."""
    term = {"anti_preferred": ((1, HOSTNAME, RED),)}
    bound = ([(_red(f"x-{i}", **term), "node-0") for i in range(4)]
             + [(_red(f"y-{i}", **term), "node-1") for i in range(8)])
    # node-2 is full of plain pods: nothing red, no room
    bound += [(world.PodRec(f"z-{i}", 100, 100 * MI, 0, {}), "node-2")
              for i in range(20)]
    sample = ([_red(f"p{i}", **term) for i in range(6)]
              + [_red("p6", q=1100, **term), _red("p7", **term)])
    want = dict({f"p{i}": "node-0" for i in range(6)}, p6="node-1",
                p7="node-1")
    placed, meta = _hand_cycle(bound, sample)
    assert placed == want
    assert meta["auction_rounds"] == 3 and meta["capacity_deferred"] == 2
    assert meta["score_terms_spliced"] == 8
    assert ref.gang_misses(_judge(bound), sample, placed) == []
    with _control(NO_SPLICE).program_control():
        assert _hand_cycle(bound, sample)[0] == dict(want, p7="node-0")


def test_a_hard_weight_of_zero_splices_no_required_term():
    """hardPodAffinityWeight 0: upstream scores no required term, a fresh
    build compiles no such row, and the auction splices none."""
    from kubetpu.models.batch import batch_score_sets
    assert batch_score_sets(["ra", "raa", "pref"], 1) == ("pref", "ra")
    assert batch_score_sets(["ra", "raa"], 0) == ()
    assert batch_score_sets(["pref", "ra"], 0) == ("pref",)
    assert batch_score_sets(["spread"], 1) == ()


@pytest.mark.parametrize("mesh_shape", [(2, 4), (1, 8)])
def test_the_mesh_path_places_the_hand_worked_batch_alike(mesh_shape):
    bound, sample = _affinity_world(PREFERRED)
    placed, meta = _hand_cycle(bound, sample,
                               _hand_cell(mesh_shape=mesh_shape))
    assert placed == FULL and meta["capacity_deferred"] == 2


# ---------------------------------- seeded toy worlds of the template

def toy_cell(nodes=24, batch=32, resident_bound=32, mesh_shape=None,
             control="bf16-scores"):
    """The row in small: one init pod a node, nodes of 1,000m that hold
    TEN pods, so that a round's tie set fills and the rest go on by
    capacity, as the row's nodes do at forty."""
    row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                      ROW + ".json"))
    config = dict(
        row, name="toy-prefaffinity-24",
        cluster={"nodes": nodes, "node": {
            "cpu_milli": 1000, "memory_bytes": 34359738368, "pods": 110}},
        init_pods={"count": nodes, "template": TEMPLATE},
        scheduler={"mode": "gang", "batch_size": batch},
        mesh_shape=mesh_shape, control=control)
    world.validate(config)
    return SimpleNamespace(
        name="toy-prefaffinity-24.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: ref, control=lambda: _control(control))


SEEDS = (40, 2 ** 31 + 40, 3500000940)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(seed):
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    rounds = batch_terms_control.sample_rounds(cell, seed, nodes, init)
    # the world is what it is for: several rounds, ended by capacity
    assert len(rounds) >= 2 and rounds[0]["capacity_deferred"] > 0
    assert control_tool.reference_misses(cell, seed, nodes, init) == 0
    assert check.gang_check(cell, seed, nodes, init) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_through_the_delta_paths_term_tables(seed):
    """Three batches placed a cycle each and a third of them deleted
    again before the sample's cycle: its score-term table is the one the
    delta path rebuilt (owners bound and gone since), not a fresh
    build's."""
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    cluster, bound = check.check_cluster(cell, ref, seed, nodes, init)
    churn = [[world.measured_record(cell.config, f"churn{k}", i)
              for i in range(16)] for k in range(3)]
    sample = check.sample_records(cell, seed)
    placed, left, records = mixed._churned_cycle(cell, seed, nodes, bound,
                                                 churn, sample)
    first = records[0]
    assert first["meta"]["resync"] is False
    refresh = [s for s in first["spans"] if s["name"] == "delta-terms"]
    assert len(refresh) == 1 and refresh[0]["args"]["owners_changed"] > 0
    assert refresh[0]["args"]["score_rows"] == len(bound) + len(left)
    assert first["meta"]["score_terms_spliced"] == len(sample)
    assert all(node for _, node in left)
    for rec, node in left:
        cluster.add(rec, node)
    assert ref.gang_misses(cluster, sample, placed) == []


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_the_mesh_path_places_the_toy_world_alike(seed):
    nodes = world.node_records(toy_cell().config)
    placed = {}
    for shape in (None, (2, 4)):
        cell = toy_cell(mesh_shape=shape)
        init = world.init_records(cell.config, seed)
        _, bound = check.check_cluster(cell, ref, seed, nodes, init)
        placed[shape] = check.program_gang_cycle(
            cell, seed, nodes, bound, check.sample_records(cell, seed))
    assert placed[None] == placed[(2, 4)]
    assert all(placed[None].values())


def test_the_rows_control_fails_and_the_splices_control_is_run_beside_it():
    """``bf16-scores`` fails this row's sample (scores decide here);
    ``no-terms-match`` must not be its control (halving every raw sum
    leaves c / c_max as it was); ``no-batch-score-terms`` is run by its
    own tool and reads 0 on the seeds whose rounds fill every node of
    their tie sets: every pod is alike, so the half count misjudges only
    a node that a round left part full."""
    cell = toy_cell()
    assert cell.config["control"] == "bf16-scores"
    nodes = world.node_records(cell.config)
    broken, by_reference, halved, blind_ref = [], [], [], []
    for seed in SEEDS:
        init = world.init_records(cell.config, seed)
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, lowprec=True))
        with cell.control().program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
        with _control("no-terms-match").program_control():
            halved.append(len(check.gang_check(cell, seed, nodes, init)))
        assert batch_terms_control.reference_misses(
            cell, seed, nodes, init) == 0
        blind_ref.append(batch_terms_control.reference_misses(
            cell, seed, nodes, init, no_batch_score_terms=True))
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)
    assert halved == [0, 0, 0]
    assert all(n >= 0 for n in blind_ref)


def test_the_tool_reports_both_sides_of_the_splices_control(capsys):
    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "cell", lambda name, root=None: cell)
        assert batch_terms_control.main(
            ["--workload", cell.name, "--seeds", "40"]) == 0
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("CONTROL ")]
    row = json.loads(line[len("CONTROL "):])
    assert row["reference"] == 0 and row["program"] == 0
    assert {"reference:" + NO_SPLICE, "program:" + NO_SPLICE,
            "differs", "sample"} <= set(row)
    assert sum(r["admitted"] for r in row["sample"]) == 32
    assert row["sample"][0]["capacity_deferred"] > 0


# ------------------------------------------------- the file, the entries

@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later40")), "checkout"))


@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_is_upstreams_template_on_the_basic_rows_nodes(row):
    world.validate(row)
    mixed_row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                            "sp-mixed-5000.json"))
    basic = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                        "sp-basic-5000.json"))
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["mesh_shape"] is None and "warmup" not in row
    assert world.init_groups(row) == [(TEMPLATE, 5000)]
    assert row["measured_pods"] == {"template": TEMPLATE}
    # the template is the mixed row's, letter for letter
    assert row["templates"] == {TEMPLATE: mixed_row["templates"][TEMPLATE]}
    assert row["cluster"] == {"nodes": 5000,
                              "node": basic["cluster"]["node"]}
    assert row["scheduler"] == basic["scheduler"]
    assert row["reference"] == "interpod_terms"
    assert row["control"] == "bf16-scores"
    assert row["guarantees"][:3] == mixed_row["guarantees"][:3]
    assert "InterPodAffinity" in row["guarantees"][-1]
    for key in ("batch_size", "mode", "init_pods", "measured_pods",
                "departures", "namespace", "templates"):
        assert key in row["assumed"], key
    assert "MATTERS" in row["assumed"]["namespace"]
    assert "29/50" in row["precision"]
    assert "PLACEHOLDER" not in json.dumps(row)
    rec = world.measured_record(row, "measured", 7)
    assert rec.labels == {"color": "red"}
    assert rec.aff_preferred == ((1, HOSTNAME, RED),)
    assert not (rec.aff_required or rec.anti_required or rec.anti_preferred
                or rec.spread)
    # forty pods fill a node by cpu, long before memory or the pod limit
    node, pod = row["cluster"]["node"], row["templates"][TEMPLATE]
    assert node["cpu_milli"] // pod["cpu_milli"] == 40
    assert 40 * pod["memory_bytes"] < node["memory_bytes"]
    assert node["pods"] > 40


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_row_and_its_three_metrics(later,
                                                            later_root):
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[53:56] == list(PR40)
    if later:
        assert names[56:]
    for name, (unit, better, source) in PR40.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "device programs", "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][0] == CELL
    assert [w["name"] for w in bench["workloads"]][:6] == OLD_CELLS + [CELL]
    entry = bench["configs"][5]
    assert entry["name"] == ROW and entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{ROW}.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = spec.cell(CELL, root)
    assert cell.chips == 1 and cell.entry["traffic"] == "saturated-d4096"
    assert len(cell.entry["why"]) <= 200
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]
    # it reads every metric all five older cells read, the two of the
    # term refresh, and its own three; nothing of another row's
    for m in bench["per_layer"][:53]:
        listed = m.get("workloads", [])
        if listed[:5] == OLD_CELLS or m["name"] in (
                "term_rows_rebuilt_per_cycle.sat",
                "terms_upload_ms_per_cycle.sat"):
            assert CELL in listed, m["name"]
        else:
            assert CELL not in listed, m["name"]
    assert set(cell.readers()) >= set(PR40)


# --------------------------------------------------- the count, by hand

def test_preferred_terms_ops_against_a_hand_count():
    # 4 pods x 1 term x 10 bound pods x (compare + and + namespace), one
    # round: 4 pods x 10 matched adds, 4 pods x 6 nodes x 4 to normalise
    assert preferred_terms.ops(4, 6, 1, 10, 1.0, 1.0, 10.0) \
        == 4 * 10 * 3 + 4 * 10 + 4 * 6 * 4
    # three rounds: at least 4 + 3 pods proposing
    assert preferred_terms.ops(4, 6, 3, 10, 1.0, 1.0, 10.0) \
        == 4 * 10 * 3 + 7 * 10 + 7 * 6 * 4
    # two labels a term: five operations a pair
    assert preferred_terms.ops(4, 6, 1, 10, 2.0, 2.0, 0.0) \
        == 4 * 2 * 10 * 5 + 4 * 6 * 4
    # no preferred term: nothing, whatever else is said
    assert preferred_terms.ops(4, 6, 3, 10, 0.0) == 0.0
    assert preferred_terms.bytes_moved(4, 10, 0.0) == 0.0
    assert preferred_terms.bytes_moved(4, 10, 1.0) == 4 * (3 * 10 + 5 * 4)


def test_the_rows_shapes_come_from_its_file_alone(row):
    assert preferred_terms.shapes_of(row, 5000, 1024, world) == {
        "terms_per_pod": 1.0, "labels_per_term": 1.0,
        "matched_node_adds_per_pod": 6024.0}
    assert existing_terms.shapes_of(row, 5000, 1024, world) == {
        "term_rows": 6024.0, "labels_per_term": 1.0,
        "matched_node_adds_per_pod": 6024.0}
    # a row whose measured pods carry no preferred term: nothing
    for other in ("sp-basic-5000", "sp-antiaffinity-5000", "sp-mixed-5000"):
        cfg = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                          other + ".json"))
        assert preferred_terms.shapes_of(cfg, 5000, 1024, world)[
            "terms_per_pod"] == 0.0
    pk = peaks.peak("TPU v5 lite")
    pref = preferred_terms.shapes_of(row, 5000, 1024, world)
    exist = existing_terms.shapes_of(row, 5000, 1024, world)
    least = preferred_terms.least_seconds(
        1024, 5000, 2.0, pk.flops_per_s, pk.bytes_per_s, 6024, pref, exist)
    plain = auction.least_seconds(1024, 5000, 2.0, pk.flops_per_s,
                                  pk.bytes_per_s)
    want = (1024 * 6024 * 3 + 1025 * 6024 + 1025 * 5000 * 4     # incoming
            + 6024 * 1024 * 3 + 1024 * 6024 * 2.0)             # existing
    assert least["preferred_ops"] == 1024 * 6024 * 3 + 1025 * 6024 \
        + 1025 * 5000 * 4
    assert least["ops_seconds"] == pytest.approx(
        plain["ops_seconds"] + want / pk.flops_per_s, rel=1e-12)
    assert least["bound"] == "operations"


def _cycle40(t, says=True, rounds=2, spliced=1024, deferred=990):
    c = base._cycle(t)
    c["meta"] = {"auction_rounds": rounds, "pods": 1024}
    if says:
        c["meta"].update(score_terms_spliced=spliced,
                         capacity_deferred=deferred)
    return c


def _ctx40(cycles, trace=None, of=CELL):
    cell = spec.cell(of, REPO)
    return cell, SimpleNamespace(
        cycles=cycles, cell=cell, trace=trace or {"modules": {}},
        device={"platform": "tpu", "kind": "TPU v5 lite"}, n_nodes=5000,
        resident_pods=6024)


def test_the_three_readers_by_hand(row):
    two = [_cycle40(0.0), _cycle40(1.0, rounds=3, spliced=1000,
                                   deferred=1500)]
    trace = {"modules": {"jit__schedule_gang(7)": {"count": 2,
                                                   "seconds": 0.1}}}
    cell, ctx = _ctx40(two, trace)
    readers = cell.readers()
    assert readers["score_terms_spliced_per_cycle.sat"](ctx) == 1012.0
    assert readers["capacity_deferred_per_cycle.sat"](ctx) == 1245.0
    pk = peaks.peak("TPU v5 lite")
    least = preferred_terms.least_seconds(
        1024, 5000, 2.5, pk.flops_per_s, pk.bytes_per_s, 6024,
        preferred_terms.shapes_of(row, 5000, 1024, world),
        existing_terms.shapes_of(row, 5000, 1024, world))
    share = readers["auction_prefscore_roofline"](ctx)
    assert share == pytest.approx(100.0 * least["seconds"] / 0.05,
                                  rel=1e-12)
    assert 0 < share < 100.0


@pytest.mark.parametrize("name", sorted(PR40))
def test_a_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent says neither counter; the share reads any program that
    ran the auction, from the configuration and the round count."""
    parent = [_cycle40(0.0, says=False), _cycle40(1.0, says=False)]
    trace = {"modules": {"jit__schedule_gang(7)": {"count": 2,
                                                   "seconds": 0.1}}}
    cell, ctx = _ctx40(parent, trace)
    got = cell.readers()[name](ctx)
    if name == "auction_prefscore_roofline":
        assert got is not None and got > 0
    else:
        assert got is None
        cell, ctx = _ctx40([_cycle40(0.0)] + parent[:1], trace)
        assert cell.readers()[name](ctx) is None
    for cycles in ([], [base._cycle(0.0)]):
        cell, ctx = _ctx40(cycles)
        assert cell.readers()[name](ctx) is None


def test_the_share_is_silent_for_a_row_without_a_preferred_term():
    trace = {"modules": {"jit__schedule_gang(7)": {"count": 2,
                                                   "seconds": 0.1}}}
    cell40 = spec.cell(CELL, REPO)
    cell, ctx = _ctx40([_cycle40(0.0)], trace, of=OLD_CELLS[0])
    assert cell40.readers()["auction_prefscore_roofline"](ctx) is None


# ------------------------------------------------ the toy, a whole run

TOY = dict(
    perfbench_toy.TOY_BASIC, name="toy-prefaffinity-48",
    cluster={"nodes": 48, "node": {"cpu_milli": 1000,
                                   "memory_bytes": 34359738368,
                                   "pods": 110}},
    init_pods={"count": 48, "template": TEMPLATE},
    measured_pods={"template": TEMPLATE},
    templates={TEMPLATE: perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]},
    scheduler={"mode": "gang", "batch_size": 32},
    reference="interpod_terms", control="bf16-scores",
    precision="as sp-prefaffinity-5000",
    guarantees=["as sp-prefaffinity-5000"])
TOY_CELL = "toy-prefaffinity-48.closed32"
TOY_TRAFFIC = dict(perfbench_toy.TOY_TRAFFIC, name="closed32", depth=64,
                   resident_bound=32,
                   warmup=dict(perfbench_toy.TOY_TRAFFIC["warmup"],
                               quiet_binds=128))
LISTED = set(PR40) | {"term_rows_rebuilt_per_cycle.sat",
                      "terms_upload_ms_per_cycle.sat",
                      "auction_rounds_per_cycle.sat",
                      "auction_admits_per_round.sat",
                      "auction_term_sets_live_per_cycle.sat"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy40")))
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
    assert set(got) & set(PR40) == set(PR40) - {"auction_prefscore_roofline"}
    # every pod of every batch carries the one term
    assert 1 <= got["score_terms_spliced_per_cycle.sat"] <= 32
    assert got["auction_term_sets_live_per_cycle.sat"] == 1.0
    # nodes of ten: rounds end by capacity, and the table turns over
    assert got["auction_rounds_per_cycle.sat"] > 1.0
    assert got["capacity_deferred_per_cycle.sat"] > 0
    assert got["term_rows_rebuilt_per_cycle.sat"] >= 48


def test_every_cycle_of_the_toy_run_says_what_it_spliced(toy_traced):
    res, ctx, said = toy_traced
    ran = [c["meta"] for c in ctx["cycles"]
           if c["meta"].get("auction_rounds")]
    assert ran
    for m in ran:
        assert m["term_sets_live"] == ["pref"] and m["needs_topo"] == 1
        assert m["score_terms_spliced"] == m["pods"]
        assert 0 <= m["capacity_deferred"] <= m["pods"] * m["auction_rounds"]
        if m["auction_rounds"] == 1:
            assert m["capacity_deferred"] == 0
    assert any(m["capacity_deferred"] > 0 for m in ran)


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_toy_is_correct_on_two_more_seeds(toy_root, toy_traced, seed):
    res, _, said = _whole_run(toy_root, seed, False)
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    assert res["metrics"]["pods_bound_per_s"]["value"] > 0
