"""BENCHMARK.json against its contract, and every name in it resolved to
the file that holds it."""

import json
import os
import re

import pytest

import perfbench_toy
from perfbench.lib import spec, traffic, world
from perfbench.tools import later_pr_tree

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(spec.ROOT, "BENCHMARK.json")) < 65536
    assert isinstance(BENCH["run_seconds"], int)
    assert 10 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("entry", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_keeps_to_the_contract(entry):
    per_layer = entry in BENCH["per_layer"]
    keys = {"name", "unit", "better", "source"}
    keys |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(entry) - {"workloads"} == keys
    assert NAME.match(entry["name"]) and UNIT.match(entry["unit"])
    assert entry["better"] in ("lower", "higher")
    assert entry["source"] in SOURCES
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    if per_layer:
        assert entry["moves"] in e2e
        assert 1 <= len(entry["layer"]) <= 200
        # each cell that reads it reports the metric it should move
        for w in entry.get("workloads", CELLS):
            assert w in e2e[entry["moves"]].get("workloads", CELLS)
    else:
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.25
    for w in entry.get("workloads", []):
        assert w in CELLS


def test_names_are_unique_and_setup_s_is_everywhere():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    setup = next(m for m in BENCH["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


# --------------------------------------------------------------------------
# What holds for ANY configuration: the cells of BENCHMARK.json, the toy
# cells a temporary checkout adds as data (perfbench_toy.py) and the row a
# later PR adds at its real size (tools/later_pr_tree.py), by one rule.


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return perfbench_toy.make_root(str(tmp_path_factory.mktemp("toyspec")))


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("laterspec")), "checkout"))


TOY_CELLS = [c["name"] + ".closed" for c in (
    perfbench_toy.TOY_CONFIG, perfbench_toy.TOY_BASIC,
    perfbench_toy.TOY_MIXED)]
ANY_CELLS = ([(name, "repo") for name in CELLS]
             + [(name, "toy") for name in TOY_CELLS]
             + [(later_pr_tree.CELL, "later")])


@pytest.fixture
def roots(request):
    """Where a cell of ANY_CELLS lives; a root is built when first asked
    for."""
    return lambda where: spec.ROOT if where == "repo" \
        else request.getfixturevalue(where + "_root")


def _cell(name, where, roots):
    root = roots(where)
    return spec.cell(name, root), spec.load_benchmark(root)


@pytest.mark.parametrize("name,where", ANY_CELLS,
                         ids=[n for n, _ in ANY_CELLS])
def test_any_cell_resolves_its_files_by_name(name, where, roots):
    cell, bench = _cell(name, where, roots)
    assert cell.config["name"] == cell.entry["config"]
    assert cell.traffic["name"] == cell.entry["traffic"]
    assert cell.chips == cell.config["chips"] == cell.entry["chips"]
    traffic.validate(cell.traffic)
    assert len(cell.entry["why"]) <= 200
    # setup_s, another end-to-end metric, and a per-layer metric
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    readers = cell.readers()
    assert readers and set(readers) == {m["name"] for m in cell.per_layer}
    assert all(callable(r) for r in readers.values())
    ref = cell.reference()
    for fn in ("Cluster", "replay", "gang_misses", "auction_schedule"):
        assert hasattr(ref, fn)
    # the control is a file found by name, with both of its halves
    assert cell.control_file == os.path.join(
        cell.root, "perfbench", "controls", cell.config["control"] + ".py")
    control = cell.control()
    assert isinstance(control.REFERENCE_KW, dict) and control.REFERENCE_KW
    assert callable(control.program_control)
    # the configuration says what it is
    for key in ("source", "cluster", "templates", "scheduler",
                "guarantees", "assumed", "reduced", "control",
                "precision", "reference", "init_pods", "measured_pods"):
        assert key in cell.config, key
    assert cell.config["reduced"] == next(
        c["reduced"] for c in bench["configs"]
        if c["name"] == cell.config["name"])


@pytest.mark.parametrize("name,where", ANY_CELLS,
                         ids=[n for n, _ in ANY_CELLS])
def test_any_configuration_builds_a_world_that_holds(name, where, roots):
    config = _cell(name, where, roots)[0].config
    world.validate(config)
    nodes = {n.name: n for n in world.node_records(config)}
    assert len(nodes) == config["cluster"]["nodes"]
    groups = world.init_groups(config)
    init = world.init_records(config, seed=2 ** 31 + 5)
    # as many as the file states, in the file's order of templates
    assert len(init) == sum(count for _, count in groups)
    assert [rec.name for rec, _ in init] == [f"init-{j}"
                                             for j in range(len(init))]
    # deterministic in the seed, different across seeds
    assert init == world.init_records(config, seed=2 ** 31 + 5)
    assert init != world.init_records(config, seed=6)
    # every template a pod list names exists
    for template, _ in groups:
        assert template in config["templates"]
    assert config["measured_pods"]["template"] in config["templates"]
    world.measured_record(config, "measured", 1234)
    # no node of the init placement over its allocatable
    used = {}
    for rec, node in init:
        u = used.setdefault(node, [0, 0, 0])
        u[0] += rec.cpu_milli
        u[1] += rec.mem_bytes
        u[2] += 1
    for node, (cpu, mem, count) in used.items():
        n = nodes[node]
        assert cpu <= n.cpu_milli and mem <= n.mem_bytes \
            and count <= n.pods, node


# --------------------------------------------------------------------------
# What is TRUE OF A NAMED ROW, keyed by the row's name: upstream's numbers.

ROWS = {
    "sp-basic-5000": {"init": 5000, "groups": 10, "anti": False},
    "sp-antiaffinity-5000": {"init": 1000, "groups": 1, "anti": True},
}


@pytest.mark.parametrize("where", ["repo", "toy", "later"])
def test_every_named_row_is_in_the_benchmark_whatever_rows_are_added(
        where, roots):
    """The rows whose facts are written down here are rows of the
    benchmark.  A row that a later PR adds as files (three toy rows in
    the toy root, a row of ``sp-mixed-5000``'s shape in the later PR's
    tree, each appended to a copy of the real BENCHMARK.json) is held by
    the tests of ANY configuration above, and needs no line in this
    file, which that PR could not edit."""
    bench = spec.load_benchmark(roots(where))
    configs = {c["name"] for c in bench["configs"]}
    assert set(ROWS) <= configs
    if where != "repo":
        assert configs - set(ROWS)       # the copy does hold rows added


def test_the_later_prs_tree_adds_row_3_at_its_size_and_edits_nothing(
        later_root):
    """tools/later_pr_tree.py: upstream's MixedSchedulingBasePod at 5000
    nodes as files and entries only."""
    was, now = BENCH, spec.load_benchmark(later_root)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(now[group]) >= len(was[group])
        for a, b in zip(was[group], now[group]):
            # an entry that was there is what it was; a cell added later
            # lists itself after the cells it listed
            assert {k: v for k, v in a.items() if k != "workloads"} \
                == {k: v for k, v in b.items() if k != "workloads"}
            assert b.get("workloads", [])[:len(a.get("workloads", []))] \
                == a.get("workloads", [])
    assert {k: now[k] for k in ("command", "paths", "run_seconds")} \
        == {k: was[k] for k in ("command", "paths", "run_seconds")}
    for top, _dirs, files in os.walk(os.path.join(spec.ROOT, "perfbench")):
        if "__pycache__" in top or ".scratch" in top:
            continue
        for name in files:
            path = os.path.join(top, name)
            with open(path, "rb") as a, open(os.path.join(
                    later_root, os.path.relpath(path, spec.ROOT)), "rb") as b:
                assert a.read() == b.read(), path
    cell = spec.cell(later_pr_tree.CELL, later_root)
    assert set(later_pr_tree.READERS) <= set(cell.readers())
    # the reader that lists no cell is read in the cells that were there
    assert later_pr_tree.READERS[1] in spec.cell(CELLS[0],
                                                 later_root).readers()
    assert later_pr_tree.READERS[0] not in spec.cell(CELLS[0],
                                                     later_root).readers()
    config = cell.config
    assert config["templates"] == {
        t: perfbench_toy.UPSTREAM_TEMPLATES[t]
        for t in perfbench_toy.MIXED_INIT}
    nodes = world.node_records(config)
    assert len(nodes) == 5000
    assert {n.labels[world.ZONE] for n in nodes} == {"zone1"}
    init = world.init_records(config, seed=2 ** 31 + 5)
    assert [rec.labels.get("color") for rec, _ in init[::2000]] == [
        None, "blue", "green", "red", "yellow"]
    per_node = {}
    for rec, node in init:
        per_node.setdefault(node, []).append(rec.labels.get("color"))
    # 10,000 round-robin over 5,000: two a node, of two templates, and
    # never two green pods (their required hostname anti-affinity)
    assert len(per_node) == 5000
    assert all(len(v) == 2 and v[0] != v[1] for v in per_node.values())
    m = world.measured_record(config, "measured", 1234)
    assert m.labels == {} and (m.cpu_milli, m.mem_bytes) == (100, 500 << 20)


@pytest.mark.parametrize("name", sorted(ROWS))
def test_the_named_row_builds_the_upstream_cluster(name):
    row = ROWS[name]
    config = spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                         name + ".json"))
    assert config["cluster"]["nodes"] == 5000
    nodes = world.node_records(config)
    assert len(nodes) == 5000
    assert (nodes[0].cpu_milli, nodes[0].mem_bytes, nodes[0].pods) == (
        4000, 32 << 30, 110)
    assert len({n.labels[world.ZONE] for n in nodes}) == 8
    assert config["init_pods"]["count"] == row["init"]
    init = world.init_records(config, seed=2 ** 31 + 5)
    assert len(init) == row["init"]
    per_node = {}
    for rec, node in init:
        per_node[node] = per_node.get(node, 0) + 1
    assert max(per_node.values()) == 1        # round-robin: one a node
    m = world.measured_record(config, "measured", 1234)
    assert (m.cpu_milli, m.mem_bytes) == (100, 500 << 20)
    groups = config["templates"][config["measured_pods"]["template"]][
        "group_labels"]
    assert groups == row["groups"]
    assert m.labels == {"app": f"app-{1234 % groups}", "group": "measured"}
    assert ("anti" in m.features) == row["anti"]
    if row["anti"]:
        # upstream's row: one shared label, selected by every pod's term,
        # so every pod -- init pods too -- excludes every other
        assert m.anti_required == ((world.HOSTNAME, (("app", "app-0"),)),)
        assert init[0][0].anti_required == m.anti_required
        assert init[0][0].labels["app"] == "app-0"
    else:
        assert m.anti_required == () and init[0][0].anti_required == ()
    # neither row holds anything a later PR's keys add
    for rec in (m, init[0][0]):
        assert (rec.aff_required, rec.anti_preferred, rec.aff_preferred,
                rec.spread, rec.node_affinity_in) == ((), (), (), (), ())


def test_a_control_that_names_no_file_fails_spec_with_the_path(tmp_path):
    root = perfbench_toy.make_root(str(tmp_path))
    path = os.path.join(root, "perfbench", "configs", "toy-basic-96.json")
    config = spec.load_json(path)
    config["control"] = "no-such-control"
    with open(path, "w") as f:
        json.dump(config, f)
    with pytest.raises(spec.SpecError) as e:
        spec.cell("toy-basic-96.closed", root)
    assert os.path.join("perfbench", "controls",
                        "no-such-control.py") in str(e.value)
    # ...and a template the world refuses fails it with the file and key
    config["control"] = "bf16-scores"
    config["templates"]["toy-plain"]["colour"] = "blue"
    with open(path, "w") as f:
        json.dump(config, f)
    with pytest.raises(spec.SpecError) as e:
        spec.cell("toy-basic-96.closed", root)
    assert path in str(e.value) and "'colour'" in str(e.value)


@pytest.mark.parametrize("feature", world.FEATURES)
def test_each_template_feature_builds_an_api_pod(feature):
    config = {"templates": {"t": {"cpu_milli": 100, "memory_bytes": 1 << 20,
                                  "group_labels": 3,
                                  "features": [feature]}}}
    rec = world.pod_record(config, "t", "measured", 7)
    pod = world.api_pod(rec, node="node-1")
    assert pod.spec.node_name == "node-1"
    assert pod.metadata.labels == {"app": "app-1", "group": "measured"}
    aff = pod.spec.affinity
    if feature == "anti":
        (t,) = aff.pod_anti_affinity \
            .required_during_scheduling_ignored_during_execution
        assert t.topology_key == world.HOSTNAME
        assert t.label_selector.match_labels == {"app": "app-1"}
    elif feature == "aff":
        (t,) = aff.pod_affinity \
            .required_during_scheduling_ignored_during_execution
        assert t.topology_key == world.ZONE
        assert t.label_selector.match_labels == {"group": "measured"}
    elif feature in ("panti", "paff"):
        side = aff.pod_anti_affinity if feature == "panti" \
            else aff.pod_affinity
        (t,) = side.preferred_during_scheduling_ignored_during_execution
        assert t.weight == 10
    else:
        (c,) = pod.spec.topology_spread_constraints
        assert (c.max_skew, c.when_unsatisfiable) == (2, "DoNotSchedule")
    with pytest.raises(ValueError):
        world.pod_record({"templates": {"t": {
            "cpu_milli": 1, "memory_bytes": 1, "features": ["nope"]}}},
            "t", "measured", 0)
