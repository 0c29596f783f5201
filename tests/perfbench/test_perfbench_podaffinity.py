"""The row PR 46 added, ``sp-podaffinity-5000`` (upstream's
SchedulingPodAffinity: every init and measured pod carries one REQUIRED
zone pod-affinity term to its own label, every node in one zone), the
first whose measured pods turn the incoming required-affinity set live
(``ops/kernels.py`` ``interpod_filter`` ``ra_live``).

The row's file and entries; the program's gang cycle against the
reference on seeded toy worlds of the template, fresh build, delta path
and mesh; the count of ``kernels/required_affinity.py`` against a hand
count and the three readers by hand; the toy through a whole traced
run; the controls (``bf16-scores`` fails the row, ``no-required-affinity``
cannot, and fails where three zones make the filter bite:
``tools/zone_affinity_check.py``).  The worlds in which the mechanism
BITES are ``tests/test_required_affinity_zone.py``'s.  A file of its
own: a PR that adds a row adds files to the benchmark and edits none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_mixed as mixed
import test_perfbench_spans as base
from perfbench.kernels import auction, peaks, required_affinity
from perfbench.lib import check, drive, spec, world
from perfbench.reference import interpod_required as ref
from perfbench.reference import interpod_terms
from perfbench.tools import cell_controls, control as control_tool
from perfbench.tools import later_pr_tree, zone_affinity_check

REPO = perfbench_toy.REPO
ZONE = world.ZONE
ROW, CELL = "sp-podaffinity-5000", "sp-podaffinity-5000.saturated"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
             "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated",
             "sp-prefspread-5000.saturated"]
TEMPLATE = "pod-with-pod-affinity"
BLUE = (("color", "blue"),)
# name -> (unit, better, source, layer)
PR46 = {
    "required_affinity_terms_per_cycle.sat": (
        "count", "lower", "program_span", "prepare"),
    "affinity_bootstrap_pods_per_cycle.sat": (
        "count", "lower", "program_counter", "device programs"),
    "auction_reqaffinity_roofline": (
        "%", "higher", "device_trace", "device programs"),
}
# metrics that were there and now list the cell besides the all-cell ones
ALSO_LISTED = {
    "term_rows_rebuilt_per_cycle.sat", "terms_upload_ms_per_cycle.sat",
    "score_terms_spliced_per_cycle.sat", "capacity_deferred_per_cycle.sat",
    "pod_axis_rows.sat", "pod_axis_live_pct.sat", "cluster_device_mb.sat",
    "delta_pods_walked_per_cycle.sat", "snapshot_pods_copied_per_cycle.sat",
    "delta_apply_device_ms_per_cycle.sat", "delta_apply_roofline"}
NO_RA = "no-required-affinity"


_control = cell_controls.control_module


# ---------------------------------- seeded toy worlds of the template

def toy_cell(nodes=24, batch=32, resident_bound=32, mesh_shape=None,
             control="bf16-scores"):
    """The row in small: the row's file with 24 of its nodes (one zone),
    one init pod a node, batches of 32."""
    row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                      ROW + ".json"))
    config = dict(
        row, name="toy-podaffinity-24",
        cluster=dict(row["cluster"], nodes=nodes),
        init_pods={"count": nodes, "template": TEMPLATE},
        scheduler={"mode": "gang", "batch_size": batch},
        mesh_shape=mesh_shape, control=control)
    world.validate(config)
    return SimpleNamespace(
        name="toy-podaffinity-24.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: ref, control=lambda: _control(control))


SEEDS = (46, 2 ** 31 + 46, 3500000946)


@pytest.mark.parametrize("seed", SEEDS)
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(seed):
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    assert control_tool.reference_misses(cell, seed, nodes, init) == 0
    assert check.gang_check(cell, seed, nodes, init) == []


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_through_the_delta_paths_term_tables(seed):
    """Three batches placed a cycle each and a third of them deleted
    again before the sample's cycle: its score-term table (one
    hard-affinity row a bound pod, at the zone key) is the one the delta
    path rebuilt, owners bound and gone since, not a fresh build's."""
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
    assert refresh[0]["args"]["filter_rows"] == 0
    meta = first["meta"]
    assert meta["term_sets_live"] == ["ra"]
    assert meta["required_affinity_terms"] == len(sample)
    assert meta["score_terms_spliced"] == len(sample)
    assert meta["affinity_bootstrap_admits"] == 0
    build, = [s for s in first["spans"] if s["name"] == "batch-build"]
    assert build["args"]["ra_rows"] == len(sample)
    assert all(node for _, node in left)
    for rec, node in left:
        cluster.add(rec, node)
    assert ref.gang_misses(cluster, sample, placed) == []


@pytest.mark.parametrize("mesh_shape", [(2, 4), (1, 8)])
def test_the_mesh_path_places_the_toy_world_alike(mesh_shape):
    seed = SEEDS[0]
    nodes = world.node_records(toy_cell().config)
    placed = {}
    for shape in (None, mesh_shape):
        cell = toy_cell(mesh_shape=shape)
        init = world.init_records(cell.config, seed)
        cluster, bound = check.check_cluster(cell, ref, seed, nodes, init)
        sample = check.sample_records(cell, seed)
        placed[shape] = check.program_gang_cycle(cell, seed, nodes, bound,
                                                 sample)
        assert ref.gang_misses(cluster, sample, placed[shape]) == []
    assert placed[None] == placed[mesh_shape]
    assert all(placed[None].values())


# ------------------------------------------------------- the controls

def test_the_rows_control_fails_and_the_filters_control_cannot():
    """``bf16-scores`` fails this row's sample: the resource scores
    decide every placement.  ``no-required-affinity`` reads 0 in the
    program's place and in the reference's: every node lies in the one
    zone, so a filter that admits everything admits what the real one
    does.  That is what the cell's ``correct`` cannot see."""
    cell = toy_cell()
    assert cell.config["control"] == "bf16-scores"
    nodes = world.node_records(cell.config)
    opened = _control(NO_RA)
    broken, by_reference = [], []
    for seed in SEEDS:
        init = world.init_records(cell.config, seed)
        by_reference.append(control_tool.reference_misses(
            cell, seed, nodes, init, lowprec=True))
        with cell.control().program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
        assert control_tool.reference_misses(
            cell, seed, nodes, init, **opened.REFERENCE_KW) == 0
        with opened.program_control():
            assert check.gang_check(cell, seed, nodes, init) == []
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)


def test_cell_controls_reads_both_beside_each_other(capsys):
    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "cell", lambda name, root=None: cell)
        assert cell_controls.main(
            ["--workload", cell.name, "--seeds", "46",
             "--controls", NO_RA]) == 0
    line, = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("CONTROLS ")]
    got = json.loads(line[len("CONTROLS "):])
    assert got["reference"] == 0 and got["program"] == 0
    assert got["reference:bf16-scores"] >= 1
    assert got["program:bf16-scores"] >= 1
    assert got["reference:" + NO_RA] == 0 and got["program:" + NO_RA] == 0


def test_three_zones_make_the_filters_control_fail(capsys):
    """``tools/zone_affinity_check.py`` on the toy: the row's nodes in
    three zones, every bound pod in one.  The program reads 0 in both
    worlds; the control reads 0 where the bound pods OWN the term (their
    score rows hold the zone alone) and every placement where they carry
    the labels only."""
    cell = toy_cell()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(spec, "cell", lambda name, root=None: cell)
        assert zone_affinity_check.main(
            ["--workload", cell.name, "--seeds", "46"]) == 0
    rows = {r["world"]: r for r in (
        json.loads(ln[len("ZONES "):])
        for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("ZONES "))}
    assert set(rows) == {"owners", "labels"}
    for r in rows.values():
        assert r["reference"] == 0 and r["program"] == 0
        assert r["program_zones"] == {"zone1": 32}
        assert r["nodes"] == 24 and r["bound"] == 24 + 64
    assert rows["owners"]["program:" + NO_RA] == 0
    assert rows["owners"]["reference:" + NO_RA] == 0
    assert rows["labels"]["program:" + NO_RA] == 32
    assert rows["labels"]["reference:" + NO_RA] == 32
    assert "zone1" not in rows["labels"]["control_zones"]


def test_the_zoned_world_binds_every_pod_in_the_home_zone():
    cell = zone_affinity_check.zoned(toy_cell())
    assert cell.config["cluster"]["node_labels"] == {
        ZONE: ["zone1", "zone2", "zone3"]}
    for owners in (True, False):
        nodes, bound = zone_affinity_check.zone_world(cell, 46, owners)
        zone = {n.name: n.labels[ZONE] for n in nodes}
        assert {zone[node] for _, node in bound} == {"zone1"}
        assert len(bound) == 24 + 64
        assert all(bool(rec.aff_required) is owners and
                   rec.labels == {"color": "blue"} for rec, _ in bound)


# ------------------------------------------------- the file, the entries

@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later46")), "checkout"))


@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_is_upstreams_template_on_the_mixed_rows_nodes(row):
    world.validate(row)
    mixed_row = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                            "sp-mixed-5000.json"))
    pref = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       "sp-prefaffinity-5000.json"))
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["mesh_shape"] is None and "warmup" not in row
    assert world.init_groups(row) == [(TEMPLATE, 5000)]
    assert row["measured_pods"] == {"template": TEMPLATE}
    # the template and the nodes are the mixed row's, letter for letter
    assert row["templates"] == {TEMPLATE: mixed_row["templates"][TEMPLATE]}
    assert row["cluster"] == mixed_row["cluster"]
    assert row["cluster"]["node_labels"] == {ZONE: ["zone1"]}
    assert row["scheduler"] == pref["scheduler"]
    assert row["control"] == "bf16-scores"
    # the reference is interpod_terms with one switch more
    assert row["reference"] == "interpod_required"
    assert issubclass(ref.Cluster, interpod_terms.Cluster)
    assert ref.gang_misses is interpod_terms.gang_misses
    assert row["guarantees"][:3] == pref["guarantees"][:3]
    assert row["guarantees"][4] == pref["guarantees"][4]
    assert "vacuously" not in row["guarantees"][3]
    assert "matches ALL" in row["guarantees"][3]
    assert "InterPodAffinity" in row["guarantees"][-1]
    for key in ("batch_size", "mode", "init_pods", "measured_pods",
                "departures", "namespace", "templates", "zone"):
        assert key in row["assumed"], key
    assert "bootstrap" in row["assumed"]["init_pods"]
    assert "failure-domain.beta.kubernetes.io/zone" in row["assumed"]["zone"]
    assert "wrongly ADMITTED" in row["precision"]
    assert "PLACEHOLDER" not in json.dumps(row)
    rec = world.measured_record(row, "measured", 7)
    assert rec.labels == {"color": "blue"}
    assert rec.aff_required == ((ZONE, BLUE),)
    assert not (rec.aff_preferred or rec.anti_required or rec.anti_preferred
                or rec.spread)
    assert world.init_records(row, 46)[0][0].aff_required == rec.aff_required
    # every node carries the one zone value
    assert {n.labels[ZONE] for n in world.node_records(row)} == {"zone1"}


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_row_and_its_three_metrics(later,
                                                            later_root):
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[61:64] == list(PR46)
    if later:
        assert names[64:]
    for name, (unit, better, source, layer) in PR46.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][0] == CELL
    assert [w["name"] for w in bench["workloads"]][:8] == OLD_CELLS + [CELL]
    entry = bench["configs"][7]
    assert entry["name"] == ROW and entry["reduced"] == []
    assert entry["file"] == f"perfbench/configs/{ROW}.json"
    assert "SchedulingPodAffinity" in entry["source"]
    assert "5000Nodes" in entry["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    cell = spec.cell(CELL, root)
    assert cell.chips == 1 and cell.entry["traffic"] == "saturated-d4096"
    assert len(cell.entry["why"]) <= 200
    assert "ADMITTED" in cell.entry["why"]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]
    # it reads every metric all seven older cells read, the term
    # refresh's, the splice's and the pod axis's, and its own three;
    # nothing of another row's
    for m in bench["per_layer"][:61]:
        listed = m.get("workloads", [])
        if listed[:7] == OLD_CELLS or m["name"] in ALSO_LISTED:
            assert CELL in listed, m["name"]
        else:
            assert CELL not in listed, m["name"]
    assert set(cell.readers()) >= set(PR46) | ALSO_LISTED
    assert cell.reference().__name__.endswith("interpod_required")
    assert cell.control().REFERENCE_KW == {"lowprec": True}


# --------------------------------------------------- the count, by hand

def test_required_affinity_ops_against_a_hand_count():
    # 4 pods x 1 term against 10 bound pods (compare + and + namespace),
    # 10 matched adds a term, one verdict a (pod, node) over 6 nodes;
    # 10 score rows against 4 pods (3 a pair), 10 matched adds a pod,
    # 4 to normalise a (pod, node)
    assert required_affinity.ops(4, 6, 10, 1.0, 1.0, 10.0, 10.0, 1.0,
                                 10.0) \
        == 4 * 10 * 3 + 4 * 10 + 4 * 6 + 4 * (10 * 3 + 10 + 6 * 4)
    # two terms of two labels a pod, nothing matched, no score row
    assert required_affinity.ops(4, 6, 10, 2.0, 2.0) \
        == 8 * 10 * 5 + 8 * 6
    # no required term: nothing, whatever else is said
    assert required_affinity.ops(4, 6, 10, 0.0, 1.0, 10.0, 10.0, 1.0,
                                 10.0) == 0.0
    assert required_affinity.bytes_moved(4, 10, 0.0, 10.0) == 0.0
    assert required_affinity.bytes_moved(4, 10, 1.0, 10.0) \
        == 4 * (3 * 10 + 5 * 4 + 7 * 10)


def test_the_rows_shapes_come_from_its_file_alone(row):
    shapes = required_affinity.shapes_of(row, 1024, world)
    assert shapes == {
        "terms_per_pod": 1.0, "labels_per_term": 1.0,
        "matched_per_term": 6024.0, "score_rows": 6024.0,
        "score_labels_per_row": 1.0, "score_rows_matched": 6024.0}
    # a row whose measured pods carry no required affinity term: nothing
    for other in ("sp-basic-5000", "sp-antiaffinity-5000",
                  "sp-prefaffinity-5000"):
        cfg = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                          other + ".json"))
        assert required_affinity.shapes_of(cfg, 1024, world)[
            "terms_per_pod"] == 0.0
    # the mixed row's measured pods are plain; 2,000 of its init pods own
    # the term and none selects a plain pod
    mixed_cfg = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                            "sp-mixed-5000.json"))
    got = required_affinity.shapes_of(mixed_cfg, 1024, world)
    assert got["terms_per_pod"] == 0.0 and got["score_rows"] == 2000.0
    assert got["score_rows_matched"] == 0.0
    pk = peaks.peak("TPU v5 lite")
    least = required_affinity.least_seconds(
        1024, 5000, pk.flops_per_s, pk.bytes_per_s, 6024, shapes)
    want = (1024 * 6024 * 3 + 1024 * 6024 + 1024 * 5000         # incoming
            + 1024 * (6024 * 3 + 6024 + 5000 * 4))              # score rows
    assert least["required_ops"] == want
    one_round = auction.least_seconds(1024, 5000, 1, pk.flops_per_s,
                                      pk.bytes_per_s)
    assert least["ops_seconds"] == pytest.approx(
        one_round["ops_seconds"] + want / pk.flops_per_s, rel=1e-12)
    assert least["bound"] == "operations"


def _cycle46(t, says=True, rounds=2, terms=1024, boot=0, pods=1024):
    c = base._cycle(t)
    c["meta"] = {"auction_rounds": rounds, "pods": pods}
    build = base._span("batch-build", t + 0.26, t + 0.29, pods=pods)
    if says:
        c["meta"].update(required_affinity_terms=terms,
                         affinity_bootstrap_admits=boot)
        build["args"]["ra_rows"] = terms
    c["spans"].append(build)
    return c


def _ctx46(cycles, trace=None, of=CELL):
    cell = spec.cell(of, REPO)
    return cell, SimpleNamespace(
        cycles=cycles, cell=cell, trace=trace or {"modules": {}},
        device={"platform": "tpu", "kind": "TPU v5 lite"}, n_nodes=5000,
        resident_pods=6024)


TRACE = {"modules": {"jit__schedule_gang(7)": {"count": 2, "seconds": 0.1}}}


def test_the_three_readers_by_hand(row):
    two = [_cycle46(0.0), _cycle46(1.0, rounds=3, terms=1000, boot=1,
                                   pods=1000)]
    cell, ctx = _ctx46(two, TRACE)
    readers = cell.readers()
    assert readers["required_affinity_terms_per_cycle.sat"](ctx) == 1012.0
    assert readers["affinity_bootstrap_pods_per_cycle.sat"](ctx) == 0.5
    pk = peaks.peak("TPU v5 lite")
    least = required_affinity.least_seconds(
        1012, 5000, pk.flops_per_s, pk.bytes_per_s, 6024,
        required_affinity.shapes_of(row, 1024, world))
    share = readers["auction_reqaffinity_roofline"](ctx)
    assert share == pytest.approx(100.0 * least["seconds"] / 0.05,
                                  rel=1e-12)
    assert 0 < share < 100.0


def test_the_share_counts_a_cycle_once_whatever_its_rounds():
    """The yardstick is the row's work a cycle: a program that takes
    five rounds for the same placements reads the same least time."""
    cell, ctx = _ctx46([_cycle46(0.0, rounds=1)], TRACE)
    once = cell.readers()["auction_reqaffinity_roofline"](ctx)
    cell, ctx = _ctx46([_cycle46(0.0, rounds=5)], TRACE)
    assert cell.readers()["auction_reqaffinity_roofline"](ctx) == once


@pytest.mark.parametrize("name", sorted(PR46))
def test_a_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent says neither count; the share reads any program that
    ran the auction, from the configuration and the cycle's pods."""
    parent = [_cycle46(0.0, says=False), _cycle46(1.0, says=False)]
    cell, ctx = _ctx46(parent, TRACE)
    got = cell.readers()[name](ctx)
    if name == "auction_reqaffinity_roofline":
        assert got is not None and got > 0
    else:
        assert got is None
        # one cycle that says beside one that does not: the span's reader
        # refuses the mixture, the counter's reads the cycles whose meta
        # names a required term (the parent's name none)
        cell, ctx = _ctx46([_cycle46(0.0)] + parent[:1], TRACE)
        assert cell.readers()[name](ctx) == (
            None if name.startswith("required") else 0.0)
    for cycles in ([], [base._cycle(0.0)]):
        cell, ctx = _ctx46(cycles)
        assert cell.readers()[name](ctx) is None


def test_the_bootstrap_reader_skips_cycles_without_a_required_term():
    plain = _cycle46(0.0, terms=0)
    del plain["meta"]["affinity_bootstrap_admits"]
    cell, ctx = _ctx46([plain, _cycle46(1.0, boot=3)], TRACE)
    readers = cell.readers()
    assert readers["affinity_bootstrap_pods_per_cycle.sat"](ctx) == 3.0
    assert readers["required_affinity_terms_per_cycle.sat"](ctx) == 512.0
    cell, ctx = _ctx46([plain], TRACE)
    assert cell.readers()["affinity_bootstrap_pods_per_cycle.sat"](
        ctx) is None


def test_the_share_is_silent_for_a_row_without_a_required_term():
    cell46 = spec.cell(CELL, REPO)
    for other in (OLD_CELLS[0], OLD_CELLS[2]):
        cell, ctx = _ctx46([_cycle46(0.0)], TRACE, of=other)
        assert cell46.readers()["auction_reqaffinity_roofline"](ctx) is None


# ------------------------------------------------ the toy, a whole run

TOY = dict(
    perfbench_toy.TOY_BASIC, name="toy-podaffinity-96",
    cluster={"nodes": 96, "node": perfbench_toy.NODE,
             "node_labels": {ZONE: ["zone1"]}},
    init_pods={"count": 96, "template": TEMPLATE},
    measured_pods={"template": TEMPLATE},
    templates={TEMPLATE: perfbench_toy.UPSTREAM_TEMPLATES[TEMPLATE]},
    scheduler={"mode": "gang", "batch_size": 32},
    reference="interpod_required", control="bf16-scores",
    precision="as sp-podaffinity-5000",
    guarantees=["as sp-podaffinity-5000"])
TOY_CELL = "toy-podaffinity-96.closed"
LISTED = set(PR46) | {"term_rows_rebuilt_per_cycle.sat",
                      "terms_upload_ms_per_cycle.sat",
                      "score_terms_spliced_per_cycle.sat",
                      "capacity_deferred_per_cycle.sat",
                      "auction_rounds_per_cycle.sat",
                      "auction_admits_per_round.sat",
                      "auction_term_sets_live_per_cycle.sat",
                      "window_compiles.sat"}


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy46")))
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
    assert set(got) & set(PR46) == set(PR46) - {
        "auction_reqaffinity_roofline"}
    # every pod of every batch carries the one term and is spliced
    assert 1 <= got["required_affinity_terms_per_cycle.sat"] <= 32
    assert got["score_terms_spliced_per_cycle.sat"] \
        == got["required_affinity_terms_per_cycle.sat"]
    assert got["auction_term_sets_live_per_cycle.sat"] == 1.0
    # 96 blue pods never leave the one zone: no pod needs the bootstrap
    assert got["affinity_bootstrap_pods_per_cycle.sat"] == 0.0
    # forty slots a node: nothing ends a round but the batch
    assert got["capacity_deferred_per_cycle.sat"] == 0.0
    # the table turns over: every bound pod owns a score row
    assert got["term_rows_rebuilt_per_cycle.sat"] >= 96


def test_every_cycle_of_the_toy_run_says_what_it_matched(toy_traced):
    res, ctx, said = toy_traced
    ran = [c for c in ctx["cycles"] if c["meta"].get("auction_rounds")]
    assert ran
    for c in ran:
        m = c["meta"]
        assert m["term_sets_live"] == ["ra"] and m["needs_topo"] == 1
        assert m["required_affinity_terms"] == m["pods"]
        assert m["score_terms_spliced"] == m["pods"]
        assert m["affinity_bootstrap_admits"] == 0
        # one round admits the batch, the next finds nobody left
        assert m["auction_rounds"] <= 2 and m["capacity_deferred"] == 0
        build, = [s for s in c["spans"] if s["name"] == "batch-build"]
        assert build["args"]["ra_rows"] == m["pods"]
        assert m["term_buckets"][0] >= 0 and m["term_buckets"][1] >= 128


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_toy_is_correct_on_two_more_seeds(toy_root, toy_traced, seed):
    res, _, said = _whole_run(toy_root, seed, False)
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    assert res["metrics"]["pods_bound_per_s"]["value"] > 0
