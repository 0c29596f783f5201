"""The row PR 49 added, ``sp-nodeaffinity-5000`` (upstream's
SchedulingNodeAffinity: every init and measured pod carries one REQUIRED
node-affinity ``In`` term over two zone values, every node in the first),
the first row judged by a reference that models a node-affinity term
(``perfbench/reference/node_affinity.py``).

The reference by hand on a three-zone toy; the row's file and entries;
the program's gang cycle against the reference on seeded toy worlds of
the template; the count of ``kernels/node_affinity.py`` against a hand
count and the three readers by hand; the toy through a whole traced run;
the controls (``bf16-scores`` and ``in-needs-every-value`` fail the row,
``no-node-affinity`` cannot, and fails where three zones make the filter
bite: ``tools/nodeaffinity_zones_check.py``).  The worlds in which the
filter BITES are ``tests/test_node_affinity_zones.py``'s.  A file of its
own: a PR that adds a row adds files to the benchmark and edits none."""

import dataclasses
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.kernels import auction, node_affinity, peaks
from perfbench.lib import check, drive, spec, world
from perfbench.reference import default_plugins
from perfbench.reference import node_affinity as ref
from perfbench.tools import cell_controls, control as control_tool
from perfbench.tools import later_pr_tree, nodeaffinity_zones_check

REPO = perfbench_toy.REPO
ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
ROW, CELL = "sp-nodeaffinity-5000", "sp-nodeaffinity-5000.saturated"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
             "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated",
             "sp-prefspread-5000.saturated", "sp-podaffinity-5000.saturated"]
TEMPLATE = "pod-with-node-affinity"
IN_TWO = ((ZONE, ("zone1", "zone2")),)
# name -> (unit, better, source, layer)
PR49 = {
    "node_affinity_terms_per_cycle.sat": (
        "count", "lower", "program_span", "prepare"),
    "node_affinity_unique_selectors_per_cycle.sat": (
        "count", "lower", "program_counter", "prepare"),
    "auction_nodeaffinity_roofline": (
        "%", "higher", "device_trace", "device programs"),
}
# PR 35's seven, which list some cells and now this one
ALSO_LISTED = {
    "pod_axis_rows.sat", "pod_axis_live_pct.sat", "cluster_device_mb.sat",
    "delta_pods_walked_per_cycle.sat", "snapshot_pods_copied_per_cycle.sat",
    "delta_apply_device_ms_per_cycle.sat", "delta_apply_roofline"}
NO_NA, EVERY = "no-node-affinity", "in-needs-every-value"
MI = 1 << 20

_control = cell_controls.control_module


# ------------------------------------ the reference, by hand, three zones

def _nodes(per_zone=2, zones=3, bare=0):
    """Node i in zone i % zones (``zone1``...), then ``bare`` nodes that
    carry no zone label."""
    out = [world.NodeRec(f"node-{i}", 4000, 32 * 1024 * MI, 110,
                         {HOSTNAME: f"node-{i}",
                          ZONE: f"zone{i % zones + 1}"})
           for i in range(per_zone * zones)]
    return out + [world.NodeRec(f"bare-{i}", 4000, 32 * 1024 * MI, 110,
                                {HOSTNAME: f"bare-{i}"})
                  for i in range(bare)]


def _pod(name, term=IN_TWO, **more):
    return world.PodRec(name, 100, 500 * MI, 0, {}, node_affinity_in=term,
                        **more)


def _cluster(nodes, bound=()):
    c = ref.Cluster(nodes)
    for rec, node in bound:
        c.add(rec, node)
    return c


def test_the_feasible_set_is_the_nodes_that_carry_a_listed_value():
    nodes = _nodes(bare=2)
    c = _cluster(nodes)
    listed = [n.labels.get(ZONE) in ("zone1", "zone2") for n in nodes]
    assert c.node_affinity_ok(_pod("p")).tolist() == listed
    assert c.feasible(_pod("p")).tolist() == listed
    assert [c.terms_ok(_pod("p"), r) for r in range(len(nodes))] == listed
    # no term: every node, the bare ones too
    assert c.feasible(_pod("plain", term=())).all()
    # a value no node carries matches nothing and harms nothing
    only2 = _pod("p2", term=((ZONE, ("zone2", "zone9")),))
    assert c.feasible(only2).tolist() == [
        n.labels.get(ZONE) == "zone2" for n in nodes]
    assert not c.feasible(_pod("p9", term=((ZONE, ("zone9",)),))).any()
    # a node that LACKS the key is refused whatever is listed
    assert not c.node_affinity_ok(_pod("p"))[-2:].any()
    # several requirements are one term's expressions: ANDed
    both = _pod("pb", term=IN_TWO + ((HOSTNAME, ("node-1", "node-2")),))
    assert np.flatnonzero(c.feasible(both)).tolist() == [1]


def test_the_tie_set_lies_inside_the_listed_zones_though_zone3_is_emptier():
    nodes = _nodes()
    bound = [(_pod(f"b{i}", term=()), n.name) for i, n in enumerate(nodes)
             if n.labels[ZONE] != "zone3"]
    c = _cluster(nodes, bound)
    assert c.tie_set(_pod("p")).tolist() == [0, 1, 3, 4]
    assert c.tie_set(_pod("plain", term=())).tolist() == [2, 5]
    # the scores are default_plugins': the term moves none
    base_c = default_plugins.Cluster(nodes)
    for rec, node in bound:
        base_c.add(rec, node)
    assert (c.scores(_pod("p")) == base_c.scores(_pod("x", term=()))).all()


def test_the_auction_and_its_judge_agree_and_word_a_miss():
    nodes = _nodes()
    sample = [_pod(f"p{i}") for i in range(12)]
    got = ref.auction_schedule(_cluster(nodes), sample,
                               np.random.default_rng(49))
    zone = {n.name: n.labels[ZONE] for n in nodes}
    assert {zone[n] for n in got.values()} == {"zone1", "zone2"}
    assert ref.gang_misses(_cluster(nodes), sample, got) == []
    # one placement moved into the refused zone; one pod held back
    wrong = dict(got, p0="node-2", p1="")
    misses = ref.gang_misses(_cluster(nodes), sample, wrong)
    assert len(misses) == 2
    assert misses[0] == ("p0: node-2 outside every round's tie set "
                         "(infeasible)")
    assert misses[1] == "p1: left pending, the reference can place it"
    # under the switches: everything anywhere / nothing anywhere
    opened = ref.auction_schedule(_cluster(nodes), sample,
                                  np.random.default_rng(49),
                                  no_node_affinity=True)
    assert "zone3" in {zone[n] for n in opened.values()}
    shut = ref.auction_schedule(_cluster(nodes), sample,
                                np.random.default_rng(49),
                                in_needs_every_value=True)
    assert set(shut.values()) == {""}
    assert len(ref.gang_misses(_cluster(nodes), sample, shut)) == 12


def _log(*binds):
    return [("add", name, 0.0) for name, _ in binds] + [
        ("bind", name, node, 1.0) for name, node in binds]


def test_replay_flags_a_bind_onto_a_refused_node_and_a_stuck_pod():
    nodes = _nodes(bare=1)
    pods = {p.name: p for p in (_pod("a"), _pod("b"), _pod("c"),
                                _pod("stuck"),
                                _pod("nowhere", term=((ZONE, ("zone9",)),)))}
    init = [(_pod("i0"), "node-0")]
    binds = (("a", "node-1"), ("b", "node-2"), ("c", "bare-0"))
    readback = {"a": "node-1", "b": "node-2", "c": "bare-0"}
    out = ref.replay(nodes, init, pods, _log(*binds), readback,
                     stuck=["stuck", "nowhere"])
    assert out == [
        "required node affinity violated: b on node-2",
        "required node affinity violated: c on bare-0",
        "stuck left unschedulable; the reference can place it"]
    # a clean log is clean; an init pod is held to its term too
    assert ref.replay(nodes, init, pods, _log(("a", "node-1")),
                      {"a": "node-1"}) == []
    assert ref.replay(nodes, [(_pod("i0"), "node-5")], pods, [], {}) == [
        "required node affinity violated: i0 on node-5"]
    # what default_plugins.replay holds, it holds: capacity, double binds,
    # unknown nodes, the read-back
    small = [dataclasses.replace(n, pods=1) for n in nodes]
    out = ref.replay(small, init, pods,
                     _log(("a", "node-0"), ("b", "node-9")) + [
                         ("bind", "a", "node-1", 2.0)],
                     {"a": None})
    assert out == [
        "node node-0 over allocatable pods: 2 > 1 after a",
        "pod b bound to unknown node node-9",
        "pod a bound twice: node-0, node-1",
        "read-back: a bound to node-0, store holds None, "
        "expected 'node-0'"]


def test_any_other_unmodelled_term_still_raises():
    nodes = _nodes()
    blue = (("color", "blue"),)
    for more in ({"spread": ((1, ZONE, "DoNotSchedule", blue),)},
                 {"aff_preferred": ((1, ZONE, blue),)},
                 {"anti_preferred": ((1, ZONE, blue),)},
                 {"anti_required": ((ZONE, blue + (("tier", "db"),)),)}):
        pod = _pod("p", **more)
        with pytest.raises(NotImplementedError, match="does not model"):
            _cluster(nodes).feasible(pod)
        with pytest.raises(NotImplementedError, match="does not model"):
            _cluster(nodes).add(pod, "node-0")
    # default_plugins itself refuses the row's own records
    with pytest.raises(NotImplementedError, match="node-affinity"):
        default_plugins.Cluster(nodes).add(_pod("p"), "node-0")
    # a required one-label anti-affinity term is default_plugins' and holds
    c = _cluster(nodes, [(world.PodRec("b", 100, 500 * MI, 0,
                                       {"color": "blue"}), "node-0")])
    anti = _pod("p", anti_required=((HOSTNAME, blue),))
    assert np.flatnonzero(c.feasible(anti)).tolist() == [1, 3, 4]


def test_the_reference_imports_nothing_of_the_program():
    import ast
    with open(ref.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                for a in n.names} | {
        n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    assert imported == {"__future__", "dataclasses", "typing", "numpy",
                        "perfbench.reference"}
    assert ref.gang_misses is default_plugins.gang_misses
    assert issubclass(ref.Cluster, default_plugins.Cluster)


# ---------------------------------- seeded toy worlds of the template

def toy_cell(nodes=24, batch=32, resident_bound=32, control="bf16-scores"):
    """The row in small: the row's file with 24 of its nodes (one zone),
    one init pod a node, batches of 32."""
    row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                      ROW + ".json"))
    config = dict(
        row, name="toy-nodeaffinity-24",
        cluster=dict(row["cluster"], nodes=nodes),
        init_pods={"count": nodes, "template": TEMPLATE},
        scheduler={"mode": "gang", "batch_size": batch}, control=control)
    world.validate(config)
    return SimpleNamespace(
        name="toy-nodeaffinity-24.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: ref, control=lambda: _control(control))


SEEDS = (49, 2 ** 31 + 49, 3500000949)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(seed):
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    assert control_tool.reference_misses(cell, seed, nodes, init) == 0
    assert check.gang_check(cell, seed, nodes, init) == []


def test_the_rows_controls_fail_and_the_filters_control_cannot():
    """``bf16-scores`` fails this row's sample (the resource scores
    decide every placement) and ``in-needs-every-value`` fails every pod
    of it (every node refused: a node wrongly REFUSED shows).
    ``no-node-affinity`` reads 0 in the program's place and in the
    reference's: every node carries zone1, so a filter that admits
    everything admits what the real one does.  That is what the cell's
    ``correct`` cannot see."""
    cell = toy_cell()
    assert cell.config["control"] == "bf16-scores"
    nodes = world.node_records(cell.config)
    opened, shut = _control(NO_NA), _control(EVERY)
    broken, by_reference = [], []
    for seed in SEEDS[:2]:
        init = world.init_records(cell.config, seed)
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, lowprec=True))
        with cell.control().program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
        assert control_tool.reference_misses(
            cell, seed, nodes, init, **opened.REFERENCE_KW) == 0
        with opened.program_control():
            assert check.gang_check(cell, seed, nodes, init) == []
        assert control_tool.reference_misses(
            cell, seed, nodes, init, **shut.REFERENCE_KW) == 32
        with shut.program_control():
            misses = check.gang_check(cell, seed, nodes, init)
        assert len(misses) == 32
        assert all(m.endswith("left pending, the reference can place it")
                   for m in misses)
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)


def test_cell_controls_reads_all_three_beside_each_other(capsys):
    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "cell", lambda name, root=None: cell)
        assert cell_controls.main(
            ["--workload", cell.name, "--seeds", "49",
             "--controls", f"{NO_NA},{EVERY}"]) == 0
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("CONTROLS ")]
    got = json.loads(line[len("CONTROLS "):])
    assert got["reference"] == 0 and got["program"] == 0
    assert got["reference:bf16-scores"] >= 1
    assert got["program:bf16-scores"] >= 1
    assert got["reference:" + NO_NA] == 0 and got["program:" + NO_NA] == 0
    assert got["reference:" + EVERY] == 32 and got["program:" + EVERY] == 32


def test_three_zones_make_the_filters_control_fail(capsys):
    """``tools/nodeaffinity_zones_check.py`` on the toy: the row's nodes
    in three zones, the init pods and the residents in the two the term
    lists, the third EMPTY.  The program reads 0 with nothing in zone3;
    the control sends the whole batch there."""
    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "cell", lambda name, root=None: cell)
        assert nodeaffinity_zones_check.main(
            ["--workload", cell.name, "--seeds", "49"]) == 0
    row, = [json.loads(ln[len("ZONES "):])
            for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("ZONES ")]
    assert row["nodes"] == 24 and row["bound"] == 24 + 64
    assert set(row["bound_zones"]) == {"zone1", "zone2"}
    assert row["reference"] == 0 and row["program"] == 0
    assert "zone3" not in row["program_zones"]
    assert "zone3" not in row["reference_zones"]
    assert sum(row["program_zones"].values()) == 32
    assert row["program:" + NO_NA] == 32 == row["reference:" + NO_NA]
    assert row["control_zones"] == {"zone3": 32}
    assert row["reference_control_zones"] == {"zone3": 32}


def test_the_zoned_world_leaves_the_third_zone_empty():
    cell = nodeaffinity_zones_check.zoned(toy_cell())
    assert cell.config["cluster"]["node_labels"] == {
        ZONE: ["zone1", "zone2", "zone3"]}
    nodes, bound = nodeaffinity_zones_check.zone_world(cell, 49)
    zone = {n.name: n.labels[ZONE] for n in nodes}
    assert {zone[node] for _, node in bound} == {"zone1", "zone2"}
    assert len(bound) == 24 + 64
    assert all(rec.node_affinity_in == IN_TWO and not rec.labels
               for rec, _ in bound)


# ------------------------------------------------- the file, the entries

@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later49")), "checkout"))


@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_is_upstreams_template_on_the_pod_affinity_rows_nodes(row):
    world.validate(row)
    podaff = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                         "sp-podaffinity-5000.json"))
    basic = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                        "sp-basic-5000.json"))
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["mesh_shape"] is None and "warmup" not in row
    assert world.init_groups(row) == [(TEMPLATE, 5000)]
    assert row["measured_pods"] == {"template": TEMPLATE}
    # the nodes are the pod-affinity row's to the letter, the basic row's
    # in count and shape; the requests are the basic row's
    assert row["cluster"] == podaff["cluster"]
    assert row["cluster"]["node"] == basic["cluster"]["node"]
    assert row["cluster"]["nodes"] == basic["cluster"]["nodes"]
    assert row["cluster"]["node_labels"] == {ZONE: ["zone1"]}
    assert row["scheduler"] == basic["scheduler"]
    assert row["templates"] == {TEMPLATE: {
        "cpu_milli": 100, "memory_bytes": 524288000,
        "node_affinity_in": {"key": ZONE, "values": ["zone1", "zone2"]}}}
    # the toy's template, recalled by an earlier PR, says the same
    assert row["templates"][TEMPLATE] \
        == perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]
    assert row["control"] == "bf16-scores"
    assert row["reference"] == "node_affinity"
    assert row["guarantees"][:4] == basic["guarantees"][:4]
    assert row["guarantees"][5] == basic["guarantees"][4]
    assert "node-selector terms" in row["guarantees"][4]
    assert "NodeAffinity" in row["guarantees"][-1]
    for key in ("batch_size", "mode", "init_pods", "measured_pods",
                "departures", "namespace", "templates", "zone",
                "container_port"):
        assert key in row["assumed"], key
    assert "failure-domain.beta.kubernetes.io/zone" in row["assumed"]["zone"]
    assert "zone-0" in row["assumed"]["templates"]
    assert "wrongly ADMITTED" in row["precision"]
    assert "wrongly REFUSED" in row["precision"]
    assert "PLACEHOLDER" not in json.dumps(row)
    rec = world.measured_record(row, "measured", 7)
    assert rec.labels == {} and rec.node_affinity_in == IN_TWO
    assert (rec.cpu_milli, rec.mem_bytes) == (100, 524288000)
    assert not (rec.aff_required or rec.aff_preferred or rec.anti_required
                or rec.anti_preferred or rec.spread)
    init = world.init_records(row, 49)
    assert len(init) == 5000
    assert init[0][0].node_affinity_in == rec.node_affinity_in
    # every node carries the one zone value; zone2 is a value none carries
    assert {n.labels[ZONE] for n in world.node_records(row)} == {"zone1"}


def test_api_pod_builds_the_one_required_in_term(row):
    pod = world.api_pod(world.measured_record(row, "measured", 3))
    na = pod.spec.affinity.node_affinity
    assert pod.spec.affinity.pod_affinity is None
    assert pod.spec.affinity.pod_anti_affinity is None
    assert not na.preferred_during_scheduling_ignored_during_execution
    term, = na.required_during_scheduling_ignored_during_execution \
        .node_selector_terms
    expr, = term.match_expressions
    assert not term.match_fields
    assert (expr.key, expr.operator, list(expr.values)) == (
        ZONE, "In", ["zone1", "zone2"])
    assert not pod.spec.node_selector and not pod.metadata.labels
    # a plain record builds no affinity at all
    plain = world.api_pod(world.PodRec("x", 100, 500 * MI, 0, {}))
    assert plain.spec.affinity is None


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_row_and_its_three_metrics(later,
                                                            later_root):
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[67:70] == list(PR49)
    if later:
        assert names[70:]
    for name, (unit, better, source, layer) in PR49.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][0] == CELL
    assert [w["name"] for w in bench["workloads"]][:9] == OLD_CELLS + [CELL]
    entry = bench["configs"][8]
    assert entry["name"] == ROW and entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{ROW}.json"
    assert "SchedulingNodeAffinity" in entry["source"]
    assert "5000Nodes" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = spec.cell(CELL, root)
    assert cell.chips == 1 and cell.entry["traffic"] == "saturated-d4096"
    assert len(cell.entry["why"]) <= 200
    assert "ADMITTED" in cell.entry["why"]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]
    # it reads every metric all eight older cells read, the pod axis's
    # and the delta scatter's, and its own three; nothing of another
    # row's (no term table, no other roofline)
    for m in bench["per_layer"][:67]:
        listed = m.get("workloads", [])
        if listed[:8] == OLD_CELLS or set(OLD_CELLS) <= set(listed) \
                or m["name"] in ALSO_LISTED:
            assert CELL in listed, m["name"]
        else:
            assert CELL not in listed, m["name"]
    assert set(cell.readers()) >= set(PR49) | ALSO_LISTED
    assert not {n for n in cell.readers()
                if n.startswith(("term_rows", "terms_upload",
                                 "score_terms", "capacity_deferred"))}
    assert [n for n in cell.readers() if n.endswith("_roofline")] == [
        "delta_apply_roofline", "auction_nodeaffinity_roofline"]
    assert cell.reference().__name__.endswith("node_affinity")
    assert cell.control().REFERENCE_KW == {"lowprec": True}
    for name in (NO_NA, EVERY):
        mod = cell_controls.control_module(name, root)
        assert set(mod.REFERENCE_KW.values()) == {True}
        assert callable(mod.program_control)


# --------------------------------------------------- the count, by hand

def test_node_affinity_ops_against_a_hand_count():
    # 4 pods x 6 nodes, one expression of two values: two compares, one
    # OR and one AND into the mask a pair
    assert node_affinity.ops(4, 6, 2.0) == 4 * 6 * 4
    # two expressions of three and one values: 4 compares, 2 ORs, one
    # AND between them, one into the mask
    assert node_affinity.ops(4, 6, 4.0) == 4 * 6 * 8
    assert node_affinity.ops(4, 6, 0.0) == 0.0
    # one label id a node and key, the key and the values of each pod
    assert node_affinity.bytes_moved(4, 6, 1.0, 2.0) == 4 * (6 + 4 * 3)
    assert node_affinity.bytes_moved(4, 6, 0.0, 0.0) == 0.0


def test_the_rows_shapes_come_from_its_file_alone(row):
    shapes = node_affinity.shapes_of(row, world)
    assert shapes == {"keys_per_pod": 1.0, "values_per_pod": 2.0}
    # a row whose measured pods carry no node-affinity term: nothing
    for other in ("sp-basic-5000", "sp-podaffinity-5000",
                  "sp-prefspread-5000"):
        cfg = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                          other + ".json"))
        assert node_affinity.shapes_of(cfg, world)["values_per_pod"] == 0.0
    pk = peaks.peak("TPU v5 lite")
    least = node_affinity.least_seconds(1024, 5000, pk.flops_per_s,
                                        pk.bytes_per_s, shapes)
    assert least["node_affinity_ops"] == 1024 * 5000 * 4
    one_round = auction.least_seconds(1024, 5000, 1, pk.flops_per_s,
                                      pk.bytes_per_s)
    assert least["ops_seconds"] == pytest.approx(
        one_round["ops_seconds"] + 1024 * 5000 * 4 / pk.flops_per_s,
        rel=1e-12)
    assert least["bytes_seconds"] == pytest.approx(
        one_round["bytes_seconds"]
        + 4 * (5000 + 1024 * 3) / pk.bytes_per_s, rel=1e-12)
    assert least["bound"] == "operations"


def _cycle49(t, says=True, rounds=1, terms=1024, unique=1, pods=1024):
    c = base._cycle(t)
    c["meta"] = {"auction_rounds": rounds, "pods": pods}
    build = base._span("batch-build", t + 0.26, t + 0.29, pods=pods)
    if says:
        c["meta"].update(node_affinity_terms=terms,
                         node_affinity_unique_selectors=unique)
        build["args"].update(rna_rows=terms, rna_unique=unique)
    c["spans"].append(build)
    return c


def _ctx49(cycles, trace=None, of=CELL):
    cell = spec.cell(of, REPO)
    return cell, SimpleNamespace(
        cycles=cycles, cell=cell, trace=trace or {"modules": {}},
        device={"platform": "tpu", "kind": "TPU v5 lite"}, n_nodes=5000,
        resident_pods=6024)


TRACE = {"modules": {"jit__schedule_gang(7)": {"count": 2, "seconds": 0.02}}}


def test_the_three_readers_by_hand(row):
    two = [_cycle49(0.0), _cycle49(1.0, rounds=3, terms=1000, unique=3,
                                   pods=1000)]
    cell, ctx = _ctx49(two, TRACE)
    readers = cell.readers()
    assert readers["node_affinity_terms_per_cycle.sat"](ctx) == 1012.0
    assert readers["node_affinity_unique_selectors_per_cycle.sat"](ctx) \
        == 2.0
    pk = peaks.peak("TPU v5 lite")
    least = node_affinity.least_seconds(
        1012, 5000, pk.flops_per_s, pk.bytes_per_s,
        node_affinity.shapes_of(row, world))
    share = readers["auction_nodeaffinity_roofline"](ctx)
    assert share == pytest.approx(100.0 * least["seconds"] / 0.01,
                                  rel=1e-12)
    assert 0 < share < 100.0


def test_the_share_counts_a_cycle_once_whatever_its_rounds():
    """The yardstick is the row's work a cycle: a program that takes
    five rounds for the same placements reads the same least time."""
    cell, ctx = _ctx49([_cycle49(0.0, rounds=1)], TRACE)
    once = cell.readers()["auction_nodeaffinity_roofline"](ctx)
    cell, ctx = _ctx49([_cycle49(0.0, rounds=5)], TRACE)
    assert cell.readers()["auction_nodeaffinity_roofline"](ctx) == once


@pytest.mark.parametrize("name", sorted(PR49))
def test_a_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent says neither count; the share reads any program that
    ran the auction, from the configuration and the cycle's pods."""
    parent = [_cycle49(0.0, says=False), _cycle49(1.0, says=False)]
    cell, ctx = _ctx49(parent, TRACE)
    got = cell.readers()[name](ctx)
    if name == "auction_nodeaffinity_roofline":
        assert got is not None and got > 0
    else:
        assert got is None
        # one cycle that says beside one that does not: refused
        cell, ctx = _ctx49([_cycle49(0.0)] + parent[:1], TRACE)
        assert cell.readers()[name](ctx) is None
    for cycles in ([], [base._cycle(0.0)]):
        cell, ctx = _ctx49(cycles)
        assert cell.readers()[name](ctx) is None


def test_the_counts_skip_cycles_that_ran_no_auction():
    idle = _cycle49(0.0, rounds=0, terms=0, unique=0, pods=0)
    cell, ctx = _ctx49([idle, _cycle49(1.0)], TRACE)
    readers = cell.readers()
    assert readers["node_affinity_terms_per_cycle.sat"](ctx) == 1024.0
    assert readers["node_affinity_unique_selectors_per_cycle.sat"](ctx) \
        == 1.0


def test_the_share_is_silent_for_a_row_without_a_node_affinity_term():
    cell49 = spec.cell(CELL, REPO)
    for other in (OLD_CELLS[0], OLD_CELLS[7]):
        cell, ctx = _ctx49([_cycle49(0.0)], TRACE, of=other)
        assert cell49.readers()["auction_nodeaffinity_roofline"](ctx) is None


# ------------------------------------------------ the toy, a whole run

TOY = dict(
    perfbench_toy.TOY_BASIC, name="toy-nodeaffinity-96",
    cluster={"nodes": 96, "node": perfbench_toy.NODE,
             "node_labels": {ZONE: ["zone1"]}},
    init_pods={"count": 96, "template": TEMPLATE},
    measured_pods={"template": TEMPLATE},
    templates={TEMPLATE: perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]},
    scheduler={"mode": "gang", "batch_size": 32},
    reference="node_affinity", control="bf16-scores",
    precision="as sp-nodeaffinity-5000",
    guarantees=["as sp-nodeaffinity-5000"])
TOY_CELL = "toy-nodeaffinity-96.closed"
LISTED = set(PR49) | {"auction_rounds_per_cycle.sat",
                      "auction_admits_per_round.sat",
                      "auction_term_sets_live_per_cycle.sat",
                      "batch_rows_shared_pct.sat", "window_compiles.sat"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy49")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": TOY["name"], "source": TOY["source"],
        "file": f"perfbench/configs/{TOY['name']}.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": TOY["name"],
        "traffic": perfbench_toy.TOY_TRAFFIC["name"], "chips": 1,
        "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "pods_bound_per_s" or m["name"] in LISTED:
            m["workloads"].append(TOY_CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "perfbench", "configs",
                           TOY["name"] + ".json"), "w") as f:
        json.dump(TOY, f)
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
    assert set(got) & set(PR49) == set(PR49) - {
        "auction_nodeaffinity_roofline"}
    # every pod of every batch carries the one term; one compiled row
    assert 1 <= got["node_affinity_terms_per_cycle.sat"] <= 32
    assert got["node_affinity_unique_selectors_per_cycle.sat"] == 1.0
    # no inter-pod term, no spread constraint: no term set is live
    assert got["auction_term_sets_live_per_cycle.sat"] == 0.0
    # one class of pods a cycle
    assert got["batch_rows_shared_pct.sat"] > 0


def test_every_cycle_of_the_toy_run_says_what_it_matched(toy_traced):
    res, ctx, said = toy_traced
    ran = [c for c in ctx["cycles"] if c["meta"].get("auction_rounds")]
    assert ran
    for c in ran:
        m = c["meta"]
        assert m["term_sets_live"] == [] and m["needs_topo"] == 0
        assert m["node_affinity_terms"] == m["pods"]
        assert m["node_affinity_unique_selectors"] == 1
        assert m["pod_classes"] == 1 and m["rows_built"] == 1
        assert m["required_affinity_terms"] == 0
        build, = [s for s in c["spans"] if s["name"] == "batch-build"]
        assert build["args"]["rna_rows"] == m["pods"]
        assert build["args"]["rna_unique"] == 1


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_toy_is_correct_on_two_more_seeds(toy_root, toy_traced, seed):
    res, _, said = _whole_run(toy_root, seed, False)
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    assert res["metrics"]["pods_bound_per_s"]["value"] > 0
