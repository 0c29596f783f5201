"""The row PR 35 added, ``sigscale-150k`` (the documented large-cluster
envelope: 5,000 nodes, 145,000 init pods, 29 a node): its file and its
entries of BENCHMARK.json; its seven readers on cycle records worked out
by hand and on records of a program that does not say; the count of
``perfbench/kernels/delta_apply.py`` for one pod row and one node row,
and its share on the small trace recorded on a TPU v5e; and a toy of the
row's shape (48 nodes x 29 init pods, batches of 64) through a whole
traced run, check (b) and the row's control on three seeds.  A file of
its own: a PR that adds a row adds files to the benchmark and edits
none."""

import collections
import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.kernels import delta_apply, peaks
from perfbench.lib import check, drive, spec, world, xplane
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
ROW, CELL = "sigscale-150k", "sigscale-150k.saturated"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated"]
# the densities read side by side: 30, 1 and 2 bound pods a node
BESIDE = [CELL, "sp-basic-5000.saturated", "sp-mixed-5000.saturated"]
# name -> (unit, better, source, layer)
PR35 = {
    "pod_axis_rows.sat": ("count", "lower", "program_counter", "prepare"),
    "pod_axis_live_pct.sat": ("%", "higher", "program_counter", "prepare"),
    "cluster_device_mb.sat": ("MB", "lower", "program_counter",
                              "device programs"),
    "delta_pods_walked_per_cycle.sat": ("count", "lower", "program_span",
                                        "prepare"),
    "snapshot_pods_copied_per_cycle.sat": ("count", "lower", "program_span",
                                           "prepare"),
    "delta_apply_device_ms_per_cycle.sat": ("ms", "lower", "device_trace",
                                            "device programs"),
    "delta_apply_roofline": ("%", "higher", "device_trace",
                             "device programs"),
}
DEVICE_READERS = ("delta_apply_device_ms_per_cycle.sat",
                  "delta_apply_roofline")


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    """The benchmark after a later PR has added a row and two per-layer
    entries (tools/later_pr_tree.py)."""
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later35")), "checkout"))


@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                       ROW + ".json"))


# ------------------------------------------------- the file, the entries

def test_the_row_is_the_basic_rows_shapes_at_29_pods_a_node(row):
    world.validate(row)
    basic = spec.load_json(os.path.join(REPO, "perfbench", "configs",
                                        "sp-basic-5000.json"))
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["mesh_shape"] is None and "warmup" not in row
    assert world.init_groups(row) == [("pod-default", 145000)]
    # every shape, the scheduler section, the arithmetic, the guarantees,
    # the reference and the control are the basic row's
    for key in ("cluster", "measured_pods", "templates", "scheduler",
                "precision", "guarantees", "reference", "control"):
        assert row[key] == basic[key], key
    assert set(basic["assumed"]) < set(row["assumed"])
    assert {"controllers", "pause_pods", "hollow_nodes"} \
        <= set(row["assumed"])
    assert "DefaultPodTopologySpread" in row["assumed"]["controllers"]
    assert len(row["source"]) <= 200 and "PLACEHOLDER" not in json.dumps(row)
    # 29 init pods of 100m / 500Mi leave every node room for eleven more
    node, pod = row["cluster"]["node"], row["templates"]["pod-default"]
    assert (node["cpu_milli"] - 29 * pod["cpu_milli"]) // pod["cpu_milli"] \
        == 11
    assert 40 * pod["memory_bytes"] < node["memory_bytes"]


@pytest.mark.parametrize("seed", [35, 2 ** 31 + 35])
def test_the_seeded_round_robin_gives_every_node_exactly_29(row, seed):
    init = world.init_records(row, seed)
    assert len(init) == 145000
    per_node = collections.Counter(node for _, node in init)
    assert len(per_node) == 5000 and set(per_node.values()) == {29}
    # with resident_bound measured pods bound and the backlog pending,
    # the store holds the documented limit
    cell = spec.cell(CELL)
    assert len(init) + cell.traffic["resident_bound"] \
        + cell.traffic["depth"] == 150120


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_seven_after_the_36_that_were_there(
        later, later_root):
    """Held by name and by the place PR 35 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[36:43] == list(PR35)
    if later:
        assert names[43:]                # the copy does hold entries added
    for name, (unit, better, source, layer) in PR35.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][:3] == BESIDE
    # the row's cell is the fifth, on one chip, under the basic row's
    # traffic, and reports the one end-to-end metric the others report
    assert [w["name"] for w in bench["workloads"]][:5] == OLD_CELLS + [CELL]
    assert bench["configs"][4]["name"] == ROW
    assert bench["configs"][4]["reduced"] == []
    cell = spec.cell(CELL, root)
    assert cell.chips == 1 and cell.entry["traffic"] == "saturated-d4096"
    assert "PLACEHOLDER" not in cell.entry["why"]
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "pods_bound_per_s")["workloads"]
    # it reads every metric all four older cells read, the plain rows'
    # auction share, and its own seven
    for m in bench["per_layer"][:36]:
        listed = m.get("workloads", [])
        if listed[:4] == OLD_CELLS or m["name"] == "auction_roofline":
            assert CELL in listed[2:5], m["name"]
        else:
            assert CELL not in listed, m["name"]
    assert set(cell.readers()) >= set(PR35) | {"auction_roofline"}


# ------------------------------------------------------- cycles by hand

def _cycle35(t, says=True, bucket=262144, live=147000, walked=61000,
             copied=60000, mb=4500):
    """``_cycle32``'s shape with what PR 35's program says: the pod axis
    and the resident bytes on the meta, ``pods_walked`` on the delta
    build, ``pods_copied`` on the snapshot.  ``says`` False: the parent."""
    c = base._cycle(t)
    spans = {s["name"]: s for s in c["spans"]}
    spans["delta-build"]["args"].update(
        terms_kept=0, node_rows_dirty=2000, node_rows_refilled=0,
        pod_rows_seen=walked, pod_rows_refilled=1024)
    spans["snapshot"]["args"]["nodes"] = 5000
    c["meta"] = {"pod_bucket": bucket, "delta_rows": 2000 + walked,
                 "delta_buckets": [2048, 65536]}
    if says:
        spans["delta-build"]["args"]["pods_walked"] = walked
        spans["snapshot"]["args"]["pods_copied"] = copied
        c["meta"].update(pod_rows_live=live,
                         cluster_device_bytes=mb * 1000000)
    return c


TWO35 = [_cycle35(0.0), _cycle35(1.0, live=149000, walked=63000,
                                 copied=64000)]
WANT35 = {
    "pod_axis_rows.sat": 262144.0,
    "pod_axis_live_pct.sat": 100.0 * 148000 / 262144,
    "cluster_device_mb.sat": 4500.0,
    "delta_pods_walked_per_cycle.sat": 62000.0,
    "snapshot_pods_copied_per_cycle.sat": 62000.0,
}


def _trace(seconds=0.2, count=2, name="jit__apply_cluster_delta(123)"):
    return {"modules": {name: {"count": count, "seconds": seconds},
                        "jit__schedule_gang(7)": {"count": 2,
                                                  "seconds": 0.1}}}


def _ctx35(cycles, trace=None, of=CELL):
    cell = spec.cell(of, REPO)
    return cell, SimpleNamespace(
        cycles=cycles, cell=cell, trace=trace or {"modules": {}},
        device={"platform": "tpu", "kind": "TPU v5 lite"})


@pytest.mark.parametrize("name", sorted(WANT35))
def test_a_counter_reader_on_cycles_worked_out_by_hand(name):
    cell, ctx = _ctx35(TWO35)
    assert cell.readers()[name](ctx) == pytest.approx(WANT35[name],
                                                     rel=1e-12)
    # a cycle that ran no delta build and no snapshot span (a chained
    # cycle of an older record) is left out of the two span means
    bare = _cycle35(2.0)
    bare["spans"] = [s for s in bare["spans"]
                     if s["name"] not in ("delta-build", "snapshot")]
    bare["meta"] = {}
    cell, ctx = _ctx35(TWO35 + [bare])
    assert cell.readers()[name](ctx) == pytest.approx(WANT35[name],
                                                     rel=1e-12)
    # they read the basic row's cell the same way
    cell, ctx = _ctx35(TWO35, of=BESIDE[1])
    assert cell.readers()[name](ctx) == pytest.approx(WANT35[name],
                                                     rel=1e-12)


@pytest.mark.parametrize("name", sorted(PR35))
def test_a_reader_finds_nothing_where_the_program_does_not_say(name):
    """The parent of PR 35 says ``pod_bucket`` and nothing else of the
    five: only ``pod_axis_rows.sat`` reads it; the two of the device trace
    read any program that has run the scatter.  Never 0, never raises."""
    parent = [_cycle35(0.0, says=False), _cycle35(1.0, says=False)]
    cell, ctx = _ctx35(parent, trace=_trace())
    got = cell.readers()[name](ctx)
    if name == "pod_axis_rows.sat":
        assert got == 262144.0
    elif name in DEVICE_READERS:
        assert got is not None and got > 0
    else:
        assert got is None
        # one cycle of a run that does not say: nothing is averaged
        cell, ctx = _ctx35(TWO35 + parent[:1])
        if name != "cluster_device_mb.sat":
            assert cell.readers()[name](ctx) is None
    # no cycle, no capture: nothing, from every reader
    for cycles in ([], [base._cycle(0.0)]):
        cell, ctx = _ctx35(cycles)
        assert cell.readers()[name](ctx) is None


# --------------------------------------------------- the count, by hand

def test_delta_apply_bytes_for_one_pod_row_and_one_node_row():
    """A pod row of two labels: 2 label ids + its node's row + its two
    flags in one word = 4 words in, 4 out = 32 bytes.  A node row of
    three labels: 4 resource channels + 3 label ids = 7 words in, 7 out =
    56 bytes."""
    assert delta_apply.pod_row_bytes(2) == 32
    assert delta_apply.node_row_bytes(3) == 56
    assert delta_apply.bytes_moved(1, 2, 3, node_rows=0) == 32.0
    assert delta_apply.bytes_moved(1, 2, 3, node_rows=1) == 56.0
    assert delta_apply.bytes_moved(10, 2, 3, node_rows=4) \
        == 4 * 56.0 + 6 * 32.0
    # a program that does not say the split: every row the cheaper kind;
    # a split that cannot be (more node rows than rows) is held to rows
    assert delta_apply.bytes_moved(10, 2, 3) == 320.0
    assert delta_apply.bytes_moved(10, 2, 3, node_rows=99) == 560.0
    pk = peaks.peak("TPU v5 lite")
    least = delta_apply.least_seconds(10, 2, 3, pk.flops_per_s,
                                      pk.bytes_per_s, node_rows=4)
    assert least["bytes"] == 416.0 and least["ops"] == 52.0
    assert least["bound"] == "bytes"
    assert least["seconds"] == pytest.approx(416.0 / 819e9, rel=1e-12)
    # the row's own shapes, through lib/world.py's records
    cell = spec.cell(CELL, REPO)
    assert delta_apply.shapes_of(cell.config, world) == {
        "labels_per_pod": 2, "labels_per_node": 3}


def test_the_readers_look_for_the_scatter_under_the_programs_own_name():
    from kubetpu.models import programs
    assert delta_apply.DELTA_PROGRAM in programs._apply_cluster_delta.__name__
    for jitted in (programs._apply_cluster_delta_donated,
                   programs._apply_cluster_delta_shared):
        assert delta_apply.DELTA_PROGRAM in jitted.__name__


def test_the_two_device_readers_by_hand():
    """Two executions in 0.2 s: 100 ms each.  61,000 and 63,000 pod rows
    beside 2,000 node rows: 62,000 x 32 + 2,000 x 56 bytes at 819 GB/s
    against 100 ms."""
    cell, ctx = _ctx35(TWO35, trace=_trace())
    assert cell.readers()["delta_apply_device_ms_per_cycle.sat"](ctx) \
        == pytest.approx(100.0)
    want = 100.0 * ((62000 * 32 + 2000 * 56) / 819e9) / 0.1
    assert cell.readers()["delta_apply_roofline"](ctx) \
        == pytest.approx(want, rel=1e-9)
    assert 0 < want < 0.01
    # the parent says node_rows_dirty since PR 32 and delta_rows since
    # PR 5: its share is read the same way
    cell, ctx = _ctx35([_cycle35(0.0, says=False),
                        _cycle35(1.0, says=False, walked=63000)],
                       trace=_trace())
    assert cell.readers()["delta_apply_roofline"](ctx) \
        == pytest.approx(want, rel=1e-9)
    # a capture in which the scatter did not run: nothing
    cell, ctx = _ctx35(TWO35, trace=_trace(name="jit_other(1)"))
    for name in DEVICE_READERS:
        assert cell.readers()[name](ctx) is None


def test_the_share_on_the_small_trace_recorded_on_the_chip():
    """``testdata/v5e_small``'s stand-in for the scatter (three executions
    of a reduction over 1 MB, 2.4 us each) under the program's name, with
    the basic cell's rows a cycle (2,000 node rows, 2,400 pod rows): the
    least time for them is under what the chip took, so the share lies
    in (0, 100]."""
    small = xplane.summarize(xplane.load(
        os.path.join(REPO, "perfbench", "testdata", "v5e_small.xplane.pb")))
    (stand_in, m), = [(k, v) for k, v in small["modules"].items()
                      if "apply_delta_small" in k]
    small["modules"]["jit__apply_cluster_delta(1)"] = m
    cycles = [_cycle35(float(t), says=False, walked=2400) for t in range(3)]
    cell, ctx = _ctx35(cycles, trace=small, of=BESIDE[1])
    per = 1e3 * m["seconds"] / m["count"]
    assert cell.readers()["delta_apply_device_ms_per_cycle.sat"](ctx) \
        == pytest.approx(per)
    share = cell.readers()["delta_apply_roofline"](ctx)
    assert share == pytest.approx(
        100.0 * ((2400 * 32 + 2000 * 56) / 819e9) / (per / 1e3), rel=1e-9)
    assert 0 < share <= 100.0


# ------------------------------------------------- the row's shape, toy

TOY = dict(
    perfbench_toy.TOY_BASIC, name="toy-sigscale-48",
    cluster={"nodes": 48, "zones": 4, "node": perfbench_toy.NODE},
    init_pods={"count": 48 * 29, "template": "toy-plain"},
    scheduler={"mode": "gang", "batch_size": 64})
TOY_CELL = "toy-sigscale-48.closed64"
# saturated-d4096 in small: two batches pending, one batch resident
TOY_TRAFFIC = dict(perfbench_toy.TOY_TRAFFIC, name="closed64", depth=128,
                   resident_bound=64,
                   warmup=dict(perfbench_toy.TOY_TRAFFIC["warmup"],
                               quiet_binds=256))
SEEDS = [35, 2 ** 31 + 35, 3500000777]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """perfbench_toy's checkout with the row's toy added the same way:
    a configuration file, a traffic file, entries."""
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy35")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": TOY["name"], "source": TOY["source"],
        "file": f"perfbench/configs/{TOY['name']}.json", "reduced": [],
        "why": "toy"})
    bench["workloads"].append({
        "name": TOY_CELL, "config": TOY["name"], "traffic": "closed64",
        "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "pods_bound_per_s" or m["name"] in PR35:
            m["workloads"].append(TOY_CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    for sub, what in (("configs", TOY), ("traffic", TOY_TRAFFIC)):
        with open(os.path.join(root, "perfbench", sub,
                               what["name"] + ".json"), "w") as f:
            json.dump(what, f)
    return root


def test_the_toys_world_has_29_on_every_node(toy_root):
    cell = spec.cell(TOY_CELL, toy_root)
    init = world.init_records(cell.config, SEEDS[0])
    assert set(collections.Counter(n for _, n in init).values()) == {29}


def _whole_run(root, seed, trace):
    from kubetpu.utils import sanitize
    cell = spec.cell(TOY_CELL, root)
    said, kept = [], {}

    def keep(**kw):              # what run_cell hands the readers as ctx
        kept.update(kw)
        return SimpleNamespace(**kw)
    armed = list(sanitize._watchdogs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(drive, "SimpleNamespace", keep)
        try:
            res = drive.run_cell(cell, seed=seed, seconds=3.0, trace=trace,
                                 require_tpu=False, out=said.append)
        finally:
            # a run never takes its compile watchdog off; a test process
            # lives on
            for wd in list(sanitize._watchdogs):
                if wd not in armed:
                    sanitize.uninstall_compile_watchdog(wd)
    return res, kept, "\n".join(said)


@pytest.fixture(scope="module")
def toy_traced(toy_root):
    return _whole_run(toy_root, SEEDS[0], True)


def test_a_traced_toy_run_is_correct_and_fills_the_five_counters(toy_traced):
    res, ctx, said = toy_traced
    assert res["correct"] is True and res["failed"] == 0, said
    assert "guarantee violations" in said and ": 0  limit 0" in said
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # off the chip there is no device plane: the two of the trace say
    # nothing, the five of the program say what it did
    assert set(got) & set(PR35) == set(PR35) - set(DEVICE_READERS)
    # 1,392 init pods and a batch or two of measured ones on a 2,048-row
    # axis the run never leaves
    assert got["pod_axis_rows.sat"] == 2048.0
    assert 100.0 * 1392 / 2048 <= got["pod_axis_live_pct.sat"] \
        <= 100.0 * (1392 + 3 * 64) / 2048
    assert 0.1 < got["cluster_device_mb.sat"] < 100.0
    # at most a batch of arrivals and as many departures dirty a node
    # each, and a dirty node is walked and copied whole: 29-40 pods
    for name in ("delta_pods_walked_per_cycle.sat",
                 "snapshot_pods_copied_per_cycle.sat"):
        assert 29 <= got[name] <= 48 * 40, name


def test_every_cycle_of_the_toy_run_walks_its_dirty_nodes_whole(toy_traced):
    """k dirty nodes of m pods: k x m walked, m the 29 init pods and the
    measured pods beside them, at most the eleven a node has room for."""
    res, ctx, said = toy_traced
    builds = 0
    for c in ctx["cycles"]:
        assert c["meta"]["pod_rows_live"] <= c["meta"]["pod_bucket"] == 2048
        assert c["meta"]["cluster_device_bytes"] > 0
        spans = {s["name"]: s for s in c["spans"]}
        assert 0 <= spans["snapshot"]["args"]["pods_copied"] <= 48 * 40
        if "delta-build" not in spans:
            continue
        a = spans["delta-build"]["args"]
        builds += 1
        assert 29 * a["node_rows_dirty"] <= a["pods_walked"] \
            <= 40 * a["node_rows_dirty"]
        assert a["pods_walked"] == a["pod_rows_seen"]
        # the snapshot cloned the nodes the build then found dirty
        assert spans["snapshot"]["args"]["pods_copied"] == a["pods_walked"]
    assert builds


@pytest.mark.parametrize("seed", SEEDS[1:])
def test_the_toy_is_correct_on_two_more_seeds(toy_root, toy_traced, seed):
    """Checks (a) and (b) of a whole untraced run: the replay of the
    client's log against 1,392 init pods, and one gang cycle of 64."""
    res, _, said = _whole_run(toy_root, seed, False)
    assert res["correct"] is True and res["failed"] == 0, said
    assert said.count(": 0  limit 0") == 2, said
    assert res["metrics"]["pods_bound_per_s"]["value"] > 0


@pytest.mark.parametrize("seed", SEEDS)
def test_check_b_passes_the_tree_and_fails_the_rows_control(toy_root, seed):
    """``tools/control.py``'s two program rows at the toy's size: the
    tree as it stands 0 misses of 64, the summed scores in bfloat16 some
    (the tie sets are decided by pod count, 29 against 30 or 31)."""
    cell = spec.cell(TOY_CELL, toy_root)
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    assert check.gang_check(cell, seed, nodes, init) == []
    with cell.control().program_control():
        assert len(check.gang_check(cell, seed, nodes, init)) > 0
