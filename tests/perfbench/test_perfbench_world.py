"""What a configuration file may state about its pods and nodes
(perfbench/lib/world.py): the two rows that exist build the world they
built before PR 28, digest for digest; every pod template of upstream's
scheduler_perf is one ``templates`` entry; init-pod lists, node labels
and what the world refuses; check (b)'s residents under a literal
template; and ``toy-mixed-96``, upstream's MixedSchedulingBasePod row in
small, added as data and driven through the program's gang cycle."""

import copy
import json
import os

import pytest

import perfbench_toy
from perfbench.lib import check, spec, world
from perfbench.tools import world_digest

ZONE, HOSTNAME = world.ZONE, world.HOSTNAME

# `python3 perfbench/tools/world_digest.py --root <parent>` on the PARENT
# of PR 28 (commit bd7502d, its own lib/world.py), before that PR's edit
PARENT_DIGESTS = """
DIGEST {"config": "sp-basic-5000", "seed": 1, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "a721525aa886d9eee40e45672a0f4c82aa90655d6f60fc434734ed50289e267b", "measured": "5db8786f20fbe4277cdca94e58376c39bd9ad5c88cd8976d440b34460be7a84a", "api": "1ec8cb1d29d92681cbf8da3f96d1d9d00b65c000da37250dc04ed9480d4f514b"}
DIGEST {"config": "sp-basic-5000", "seed": 6, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "caec8d5c8ece9753737a52523403edee68ed2a256dafc448446517b23bd0c265", "measured": "5db8786f20fbe4277cdca94e58376c39bd9ad5c88cd8976d440b34460be7a84a", "api": "ec668694c0de4acc5f67e6e0e7eb8c3dc2fa319223aef2f210574b4980f08081"}
DIGEST {"config": "sp-basic-5000", "seed": 2147483653, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "8ce5f274f281ad5b5daece8f3898c9c17a0bd00cd55a5068e8692797715c7f4b", "measured": "5db8786f20fbe4277cdca94e58376c39bd9ad5c88cd8976d440b34460be7a84a", "api": "e858a71904cc573513654c4f877f1abb6c38b32f85f1db1c917edead2c224207"}
DIGEST {"config": "sp-antiaffinity-5000", "seed": 1, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "2df7d7917dbd1e7a088b2621956ae46cd911c0703b72a2bbe556009f4c0c55dc", "measured": "fb491f47c28db5d17a42614974ffe3fc5914feedd29434ed1c0524f3f984c36f", "api": "fcf333673c26fd01792ad96149fbca7c10b1d9ffdfd468b0bef748318f90e119"}
DIGEST {"config": "sp-antiaffinity-5000", "seed": 6, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "dbaaf118fcfe8c8625fc9519501c844467a37c748a42aad9503651017371ed1b", "measured": "fb491f47c28db5d17a42614974ffe3fc5914feedd29434ed1c0524f3f984c36f", "api": "6dd5f9028cf67d76ba0537c9965578134f9aa2b6378e939a3339bb9a796d8389"}
DIGEST {"config": "sp-antiaffinity-5000", "seed": 2147483653, "nodes": "23670386015175cd11b39180d2832d5ab28149f08da2c377744cf32faba51386", "init": "f3baa33396a01732bf422f2fba4342beeb865bd4d926e6c22f314d29c9acc22c", "measured": "fb491f47c28db5d17a42614974ffe3fc5914feedd29434ed1c0524f3f984c36f", "api": "a8fed19c481d5b16f4d4f6aeb21f9374cda4d25be6b3eaa5af1e06ae176a11b7"}
"""
PARENT = [json.loads(line[len("DIGEST "):])
          for line in PARENT_DIGESTS.strip().splitlines()]


@pytest.mark.parametrize(
    "want", PARENT, ids=[f"{d['config']}-{d['seed']}" for d in PARENT])
def test_the_rows_that_exist_build_the_world_the_parent_built(want):
    """Node records, init records with their placement, measured records
    0-4,095 and every API object built from them, field for field."""
    config = spec.load_json(os.path.join(
        spec.ROOT, "perfbench", "configs", want["config"] + ".json"))
    got = world_digest.digests(world, config, want["seed"])
    assert got == {k: want[k] for k in ("nodes", "init", "measured", "api")}
    # what PR 28 added to a record is empty here, so the digest of the
    # fields PR 25 gave it is the digest of the whole record
    extra = set(world.PodRec.__dataclass_fields__) \
        - set(world_digest.POD_FIELDS)
    assert extra == {"anti_preferred", "aff_preferred", "spread",
                     "node_affinity_in"}
    for rec in ([r for r, _ in world.init_records(config, want["seed"])]
                + [world.measured_record(config, "measured", i)
                   for i in range(64)]):
        assert all(getattr(rec, f) == () for f in extra)


def test_the_digest_sees_a_moved_pod_and_a_changed_label():
    config = spec.load_json(os.path.join(
        spec.ROOT, "perfbench", "configs", "sp-antiaffinity-5000.json"))
    base = world_digest.digests(world, config, 1)
    moved = world_digest.digests(world, config, 2)
    assert moved["init"] != base["init"] and moved["api"] != base["api"]
    assert moved["nodes"] == base["nodes"]
    # (a changed configuration is a new dict: world.py parses a dict's
    # templates once)
    changed = copy.deepcopy(config)
    changed["templates"]["pod-with-pod-anti-affinity"]["group_labels"] = 2
    relabelled = world_digest.digests(world, changed, 1)
    assert relabelled["measured"] != base["measured"]


# ---------------------------------------------------- upstream's templates


def _terms(side, kind):
    """[(topology key, match labels[, weight])...] of one side's list."""
    if side is None:
        return []
    if kind == "required":
        return [(t.topology_key, t.label_selector.match_labels) for t in
                side.required_during_scheduling_ignored_during_execution]
    return [(t.pod_affinity_term.topology_key,
             t.pod_affinity_term.label_selector.match_labels, t.weight)
            for t in
            side.preferred_during_scheduling_ignored_during_execution]


# template -> (labels, cpu, priority, and what the API object must hold)
TABLE = {
    "pod-default": ({}, 100, 0, {}),
    "pod-with-pod-affinity": (
        {"color": "blue"}, 100, 0,
        {"aff_required": [(ZONE, {"color": "blue"})]}),
    "pod-with-pod-anti-affinity": (
        {"color": "green"}, 100, 0,
        {"anti_required": [(HOSTNAME, {"color": "green"})]}),
    "pod-with-preferred-pod-affinity": (
        {"color": "red"}, 100, 0,
        {"aff_preferred": [(HOSTNAME, {"color": "red"}, 1)]}),
    "pod-with-preferred-pod-anti-affinity": (
        {"color": "yellow"}, 100, 0,
        {"anti_preferred": [(HOSTNAME, {"color": "yellow"}, 1)]}),
    "pod-with-topology-spreading": (
        {"color": "blue"}, 100, 0,
        {"spread": [(5, ZONE, "DoNotSchedule", {"color": "blue"})]}),
    "pod-with-preferred-topology-spreading": (
        {"color": "blue"}, 100, 0,
        {"spread": [(5, ZONE, "ScheduleAnyway", {"color": "blue"})]}),
    "pod-with-node-affinity": (
        {}, 100, 0, {"node_in": (ZONE, ["zone1", "zone2"])}),
    "pod-low-priority": ({}, 900, 0, {}),
    "pod-high-priority": ({}, 3000, 10, {}),
}


def assert_api_pod_holds(pod, labels, cpu, priority, terms):
    """The API object states exactly what the table's row states."""
    assert pod.metadata.labels == labels
    assert pod.spec.priority == priority
    (c,) = pod.spec.containers
    assert c.resources.requests == {"cpu": f"{cpu}m", "memory": "524288000"}
    aff = pod.spec.affinity
    pa = aff.pod_affinity if aff else None
    paa = aff.pod_anti_affinity if aff else None
    assert _terms(pa, "required") == terms.get("aff_required", [])
    assert _terms(paa, "required") == terms.get("anti_required", [])
    assert _terms(pa, "preferred") == terms.get("aff_preferred", [])
    assert _terms(paa, "preferred") == terms.get("anti_preferred", [])
    assert [(s.max_skew, s.topology_key, s.when_unsatisfiable,
             s.label_selector.match_labels)
            for s in pod.spec.topology_spread_constraints] \
        == terms.get("spread", [])
    na = aff.node_affinity if aff else None
    if "node_in" in terms:
        (term,) = na.required_during_scheduling_ignored_during_execution \
            .node_selector_terms
        (req,) = term.match_expressions
        assert (req.key, req.operator, req.values) == (
            terms["node_in"][0], "In", terms["node_in"][1])
    else:
        assert na is None
    if not terms:
        assert aff is None


@pytest.mark.parametrize("template", sorted(TABLE))
def test_each_upstream_template_is_one_templates_entry(template):
    assert set(TABLE) == set(perfbench_toy.UPSTREAM_TEMPLATES)
    config = {"name": "t", "templates": {
        template: perfbench_toy.UPSTREAM_TEMPLATES[template]}}
    labels, cpu, priority, terms = TABLE[template]
    for role, i in (("init", 0), ("measured", 7), ("sample", 123456)):
        rec = world.pod_record(config, template, role, i)
        # literal: the same labels and terms under every role and index
        assert rec.name == f"{role}-{i}" and rec.labels == labels
        assert rec.features == ()
        assert_api_pod_holds(world.api_pod(rec), labels, cpu, priority,
                             terms)
    bound = world.api_pod(rec, node="node-3")
    assert bound.spec.node_name == "node-3"
    # the record is plain: tuples of strings and numbers, nothing of the
    # program's, so a reference can model it
    json.dumps([getattr(rec, f) for f in world.PodRec.__dataclass_fields__])


def test_a_template_states_several_terms_and_keeps_their_order():
    t = {"cpu_milli": 1, "memory_bytes": 1, "labels": {"a": "b", "c": "d"},
         "pod_anti_affinity": [
             {"topology_key": HOSTNAME, "match_labels": {"a": "b"},
              "required": True},
             {"topology_key": ZONE, "match_labels": {"c": "d"}, "weight": 7},
             {"topology_key": ZONE, "match_labels": {"a": "b", "c": "d"},
              "required": True}]}
    rec = world.pod_record({"templates": {"t": t}}, "t", "measured", 0)
    assert rec.anti_required == (
        (HOSTNAME, (("a", "b"),)), (ZONE, (("a", "b"), ("c", "d"))))
    assert rec.anti_preferred == ((7, ZONE, (("c", "d"),)),)
    paa = world.api_pod(rec).spec.affinity.pod_anti_affinity
    assert _terms(paa, "required") == [
        (HOSTNAME, {"a": "b"}), (ZONE, {"a": "b", "c": "d"})]
    assert _terms(paa, "preferred") == [(ZONE, {"c": "d"}, 7)]


SHORTHAND = {
    "anti": {"anti_required": ((HOSTNAME, (("app", "app-1"),)),)},
    "aff": {"aff_required": ((ZONE, (("group", "measured"),)),)},
    "panti": {"anti_preferred": ((10, ZONE, (("app", "app-1"),)),)},
    "paff": {"aff_preferred": ((10, ZONE, (("app", "app-1"),)),)},
    "spread": {"spread": ((2, ZONE, "DoNotSchedule",
                           (("group", "measured"),)),)},
}


@pytest.mark.parametrize("feature", world.FEATURES)
def test_the_features_shorthand_expands_to_the_terms_it_built(feature):
    assert set(SHORTHAND) == set(world.FEATURES)
    config = {"templates": {"t": {"cpu_milli": 100, "memory_bytes": 1 << 20,
                                  "group_labels": 3,
                                  "features": [feature]}}}
    rec = world.pod_record(config, "t", "measured", 7)
    assert rec.features == (feature,)
    assert rec.labels == {"app": "app-1", "group": "measured"}
    for field in ("anti_required", "aff_required", "anti_preferred",
                  "aff_preferred", "spread", "node_affinity_in"):
        assert getattr(rec, field) == SHORTHAND[feature].get(field, ())


# ------------------------------------------ what the world refuses, and how

BAD_TEMPLATES = {
    "an unknown key": (
        {"cpu_milli": 1, "memory_bytes": 1, "colour": "blue"}, "'colour'"),
    "a term with neither required nor weight": (
        {"cpu_milli": 1, "memory_bytes": 1, "pod_affinity": [
            {"topology_key": ZONE, "match_labels": {"a": "b"}}]},
        "pod_affinity[0]"),
    "a term with both": (
        {"cpu_milli": 1, "memory_bytes": 1, "pod_anti_affinity": [
            {"topology_key": ZONE, "match_labels": {"a": "b"},
             "required": True, "weight": 3}]}, "pod_anti_affinity[0]"),
    "a term with an unknown key": (
        {"cpu_milli": 1, "memory_bytes": 1, "pod_affinity": [
            {"topology_key": ZONE, "match_labels": {"a": "b"},
             "required": True, "namespaces": ["x"]}]}, "'namespaces'"),
    "a term without a selector": (
        {"cpu_milli": 1, "memory_bytes": 1, "pod_affinity": [
            {"topology_key": ZONE, "required": True}]}, "match_labels"),
    "a spread constraint without max_skew": (
        {"cpu_milli": 1, "memory_bytes": 1, "topology_spread": [
            {"topology_key": ZONE, "when_unsatisfiable": "DoNotSchedule",
             "match_labels": {"a": "b"}}]}, "max_skew"),
    "a spread constraint that does something else when unsatisfiable": (
        {"cpu_milli": 1, "memory_bytes": 1, "topology_spread": [
            {"max_skew": 1, "topology_key": ZONE,
             "when_unsatisfiable": "Evict", "match_labels": {"a": "b"}}]},
        "when_unsatisfiable"),
    "node affinity without values": (
        {"cpu_milli": 1, "memory_bytes": 1,
         "node_affinity_in": {"key": ZONE}}, "node_affinity_in"),
    "the shorthand mixed with literal labels": (
        {"cpu_milli": 1, "memory_bytes": 1, "features": ["anti"],
         "labels": {"a": "b"}}, "'labels'"),
    "no cpu": ({"memory_bytes": 1}, "cpu_milli"),
}


@pytest.mark.parametrize("case", sorted(BAD_TEMPLATES))
def test_a_template_the_world_cannot_read_raises_with_the_key(case):
    template, said = BAD_TEMPLATES[case]
    config = {"name": "cfg-x", "templates": {"t-bad": template}}
    with pytest.raises(ValueError) as e:
        world.pod_record(config, "t-bad", "measured", 0)
    assert "cfg-x" in str(e.value) and "t-bad" in str(e.value)
    assert said in str(e.value)


def _small(**over):
    config = {
        "name": "cfg-y",
        "cluster": {"nodes": 6, "node": perfbench_toy.NODE},
        "init_pods": [{"template": "a", "count": 4},
                      {"template": "b", "count": 5}],
        "measured_pods": {"template": "a"},
        "templates": {"a": {"cpu_milli": 100, "memory_bytes": 1 << 20},
                      "b": {"cpu_milli": 200, "memory_bytes": 1 << 20,
                            "labels": {"color": "red"}}}}
    config.update(over)
    return config


def test_init_pods_may_be_a_list_built_in_its_order():
    config = _small()
    init = world.init_records(config, seed=3)
    assert [r.name for r, _ in init] == [f"init-{j}" for j in range(9)]
    assert [r.cpu_milli for r, _ in init] == [100] * 4 + [200] * 5
    assert [r.labels for r, _ in init] == [{}] * 4 + [{"color": "red"}] * 5
    # one seeded round-robin under the whole list: the first six on six
    # different nodes, the seventh back on the first pod's node
    placed = [node for _, node in init]
    assert len(set(placed[:6])) == 6 and placed[6:] == placed[:3]
    assert placed == world.init_placement(config, 3)
    assert placed != world.init_placement(config, 4)
    # the single form is the list of one
    single = _small(init_pods={"count": 4, "template": "a"})
    assert world.init_records(single, 3) == init[:4]
    assert world.init_groups(single) == [("a", 4)]
    assert world.init_groups(_small(init_pods=[])) == []
    assert world.init_records(_small(init_pods=[]), 3) == []


@pytest.mark.parametrize("init_pods,said", [
    ([{"template": "a", "count": 1}, {"template": "nope", "count": 2}],
     "init_pods[1]"),
    ([{"template": "a"}], "init_pods[0]"),
    ({"template": "a", "count": 1, "namespace": "x"}, "'namespace'"),
])
def test_an_init_list_the_world_cannot_read_raises_with_the_key(
        init_pods, said):
    config = _small(init_pods=init_pods)
    for build in (world.validate, lambda c: world.init_records(c, 1)):
        with pytest.raises(ValueError) as e:
            build(config)
        assert "cfg-y" in str(e.value) and said in str(e.value)
    with pytest.raises(ValueError) as e:
        world.validate(_small(measured_pods={"template": "gone"}))
    assert "measured_pods" in str(e.value) and "'gone'" in str(e.value)


def test_node_labels_are_data_and_zones_means_what_it_meant():
    moons = ["moon-1", "moon-2", "moon-3"]
    config = _small(cluster={"nodes": 7, "node": perfbench_toy.NODE,
                             "node_labels": {ZONE: moons, "rack": ["r0"]}})
    nodes = world.node_records(config)
    assert [n.labels[ZONE] for n in nodes] == [moons[i % 3]
                                               for i in range(7)]
    assert all(n.labels == {HOSTNAME: n.name, ZONE: n.labels[ZONE],
                            "rack": "r0"} for n in nodes)
    assert world.api_node(nodes[4]).metadata.labels == nodes[4].labels
    # zones: n, as before: zone-<i % n> and one region
    zoned = world.node_records(_small(cluster={
        "nodes": 5, "zones": 2, "node": perfbench_toy.NODE}))
    assert [n.labels[ZONE] for n in zoned] == [
        "zone-0", "zone-1", "zone-0", "zone-1", "zone-0"]
    assert all(n.labels[world.REGION] == "region-0" for n in zoned)
    # neither: the hostname alone, as upstream's node-default.yaml
    bare = world.node_records(_small())
    assert all(n.labels == {HOSTNAME: n.name} for n in bare)
    for bad, said in (({ZONE: []}, "non-empty list"),
                      ({ZONE: "zone1"}, "non-empty list"),
                      ({HOSTNAME: ["x"]}, "the node name")):
        with pytest.raises(ValueError) as e:
            world.node_records(_small(cluster={
                "nodes": 2, "node": perfbench_toy.NODE, "node_labels": bad}))
        assert "node_labels" in str(e.value) and said in str(e.value)
    with pytest.raises(ValueError) as e:
        world.validate(_small(cluster={
            "nodes": 2, "zones": 2, "node": perfbench_toy.NODE,
            "node_labels": {ZONE: ["zone1"]}}))
    assert "cluster.zones" in str(e.value)


# ------------------------------------------------ toy-mixed-96, end to end


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return perfbench_toy.make_root(str(tmp_path_factory.mktemp("toyworld")))


def test_toy_mixed_is_added_as_data_and_builds_upstreams_row(toy_root):
    cell = spec.cell("toy-mixed-96.closed", toy_root)
    with open(cell.config_file) as f:
        assert json.load(f) == perfbench_toy.TOY_MIXED   # the file is data
    config = cell.config
    nodes = world.node_records(config)
    assert len(nodes) == 96
    assert {n.labels[ZONE] for n in nodes} == {"zone1"}       # ONE zone
    assert world.REGION not in nodes[0].labels
    init = world.init_records(config, seed=2 ** 31 + 9)
    assert len(init) == 120
    # five templates x 24 in the file's order, each pod's API object term
    # by term what the file states
    for j, (rec, node) in enumerate(init):
        template = perfbench_toy.MIXED_INIT[j // 24]
        assert_api_pod_holds(world.api_pod(rec, node), *TABLE[template])
        assert world.api_pod(rec, node).spec.node_name == node
    # 120 pods round-robin over 96 nodes: 24 nodes hold two pods, of two
    # different templates (a plain one and a preferred-anti-affinity one)
    on = {}
    for rec, node in init:
        on.setdefault(node, []).append(rec)
    twice = [recs for recs in on.values() if len(recs) == 2]
    assert len(twice) == 24 and len(on) == 96
    assert all(a.labels != b.labels for a, b in twice)
    assert {(tuple(a.labels.items()), tuple(b.labels.items()))
            for a, b in twice} == {((), (("color", "yellow"),))}
    # no two green pods share a node: the init placement itself keeps the
    # required hostname anti-affinity the green template states
    greens = [node for rec, node in init
              if rec.labels == {"color": "green"}]
    assert len(greens) == len(set(greens)) == 24
    # the measured pods are plain
    assert_api_pod_holds(
        world.api_pod(world.measured_record(config, "measured", 5)),
        *TABLE["pod-default"])


def test_the_program_places_plain_pods_over_toy_mixeds_terms(toy_root):
    """One cycle of the program's own gang auction (check (b)'s driver)
    over a cluster whose existing pods carry all four kinds of pod term:
    a full batch of plain pods is placed, each on a node that holds ONE
    init pod, never two (the default plugins' resource scores; no
    existing term selects a pod without labels)."""
    cell = spec.cell("toy-mixed-96.closed", toy_root)
    seed = 2 ** 31 + 9
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    sample = check.sample_records(cell, seed)
    assert len(sample) == cell.config["scheduler"]["batch_size"] == 16
    placed = check.program_gang_cycle(cell, seed, nodes, init, sample)
    assert set(placed) == {rec.name for rec in sample}
    assert all(placed.values()), placed
    held = {}
    for _, node in init:
        held[node] = held.get(node, 0) + 1
    assert {held[node] for node in placed.values()} == {1}
    # ...and the one reference there is REFUSES this cluster, by what its
    # existing pods hold, where it used to ignore a term it did not know
    with pytest.raises(NotImplementedError) as e:
        check.gang_check(cell, seed, nodes, init)
    assert "preferred" in str(e.value)


# ---------------------------------------- check (b) under a literal template


def _check_cell(config):
    """As much of a Cell as check.check_cluster / sample_records read."""
    from types import SimpleNamespace
    from perfbench.reference import default_plugins
    return SimpleNamespace(
        config=config, traffic={"resident_bound": 4},
        reference=lambda: default_plugins)


def test_check_b_sees_what_the_window_sees_under_a_literal_template():
    """Residents and sample carry the measured template's OWN labels and
    selectors, so a sample pod's required term counts the residents as a
    measured pod's counts the window's residents."""
    green = perfbench_toy.UPSTREAM_TEMPLATES["pod-with-pod-anti-affinity"]
    config = {
        "name": "cfg-green",
        "cluster": {"nodes": 16, "node": perfbench_toy.NODE},
        "init_pods": [], "measured_pods": {"template": "green"},
        "templates": {"green": green},
        "scheduler": {"batch_size": 4}}
    cell = _check_cell(config)
    ref = cell.reference()
    nodes = world.node_records(config)
    cluster, bound = check.check_cluster(cell, ref, 5, nodes, [])
    sample = check.sample_records(cell, 5)
    measured = world.measured_record(config, "measured", 0)
    assert len(bound) == 8 and len(sample) == 4
    names = [rec.name for rec, _ in bound] + [rec.name for rec in sample]
    assert all(n.startswith(("resident-", "sample-")) for n in names)
    assert len(set(names)) == len(names)
    for rec in [rec for rec, _ in bound] + sample:
        assert rec.labels == measured.labels == {"color": "green"}
        assert rec.anti_required == measured.anti_required
    # the residents took eight nodes; a sample pod's term counts them:
    # exactly those eight are infeasible for it
    taken = {node for _, node in bound}
    assert len(taken) == 8
    feasible = cluster.feasible(sample[0])
    assert {n.name for n, ok in zip(nodes, feasible) if not ok} == taken


def test_check_b_under_a_features_template_is_what_it_was():
    """``group=<role>``: the residents are ``group=resident``, the sample
    ``group=sample``; the ``app`` label, which ``anti`` selects, is shared."""
    config = dict(perfbench_toy.TOY_CONFIG)
    cell = _check_cell(config)
    nodes = world.node_records(config)
    init = world.init_records(config, 5)
    cluster, bound = check.check_cluster(cell, cell.reference(), 5, nodes,
                                         init)
    sample = check.sample_records(cell, 5)
    residents = [rec for rec, _ in bound[len(init):]]
    assert {rec.labels["group"] for rec in residents} == {"resident"}
    assert {rec.labels["group"] for rec in sample} == {"sample"}
    assert {rec.labels["app"] for rec in residents + sample} == {"app-0"}
    taken = {node for _, node in bound}
    feasible = cluster.feasible(sample[0])
    assert {n.name for n, ok in zip(nodes, feasible) if not ok} == taken
