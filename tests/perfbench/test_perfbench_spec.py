"""BENCHMARK.json against its contract, and every name in it resolved to
the file that holds it."""

import json
import os
import re

import pytest

from perfbench.lib import spec, traffic, world

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


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves_its_files_by_name(name):
    cell = spec.cell(name)
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
    from perfbench.tools import control
    assert cell.config["control"] in control.REFERENCE_CONTROLS
    # the configuration says what it is
    for key in ("source", "cluster", "templates", "scheduler",
                "guarantees", "assumed", "reduced", "control",
                "precision"):
        assert key in cell.config, key
    assert cell.config["reduced"] == next(
        c["reduced"] for c in BENCH["configs"]
        if c["name"] == cell.config["name"])
    assert cell.config["cluster"]["nodes"] == 5000


@pytest.mark.parametrize("name", sorted({w["config"]
                                         for w in BENCH["workloads"]}))
def test_configuration_builds_the_upstream_cluster(name):
    config = spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                         name + ".json"))
    nodes = world.node_records(config)
    assert len(nodes) == 5000
    assert (nodes[0].cpu_milli, nodes[0].mem_bytes, nodes[0].pods) == (
        4000, 32 << 30, 110)
    assert len({n.labels[world.ZONE] for n in nodes}) == 8
    init = world.init_records(config, seed=2 ** 31 + 5)
    assert len(init) == config["init_pods"]["count"]
    assert init == world.init_records(config, seed=2 ** 31 + 5)
    assert init != world.init_records(config, seed=6)
    per_node = {}
    for rec, node in init:
        per_node[node] = per_node.get(node, 0) + 1
    assert max(per_node.values()) == 1        # round-robin: one a node
    m = world.measured_record(config, "measured", 1234)
    assert (m.cpu_milli, m.mem_bytes) == (100, 500 << 20)
    groups = config["templates"][config["measured_pods"]["template"]][
        "group_labels"]
    assert m.labels == {"app": f"app-{1234 % groups}", "group": "measured"}
    if "anti" in m.features:
        # upstream's row: one shared label, selected by every pod's term,
        # so every pod -- init pods too -- excludes every other
        assert groups == 1
        assert m.anti_required == ((world.HOSTNAME, (("app", "app-0"),)),)
        assert init[0][0].anti_required == m.anti_required
        assert init[0][0].labels["app"] == "app-0"


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
