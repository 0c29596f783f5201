"""``sp-mixed-5000`` (upstream's MixedSchedulingBasePod row): its
reference (perfbench/reference/interpod_terms.py) against hand-worked
upstream cases; the program's gang cycle held to that reference on
batches in which existing terms both select and do not select incoming
pods, through a fresh build and through the delta path; the row's
control seen to fail the plain sample and the mixed sample's the mixed
one; the configuration file; the existing-term count and the three
readers the row brings."""

import dataclasses
import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import perfbench_toy
from perfbench.kernels import auction, existing_terms, peaks
from perfbench.lib import check, drive, spec, world
from perfbench.reference import interpod_terms as ref
from perfbench.tools import control as control_tool
from perfbench.tools import mixed_sample_check, world_digest

ZONE, HOSTNAME = world.ZONE, world.HOSTNAME
ROW = "sp-mixed-5000"
CELL = ROW + ".saturated"


# ------------------------------------------------- the reference, by hand

def _nodes(n, zones=("z",), bare=()):
    """``n`` nodes of upstream's shape; node ``i`` in ``zones[i % len]``;
    the nodes in ``bare`` carry no zone label."""
    return [world.NodeRec(
        f"node-{i}", 4000, 32 << 30, 110,
        dict({HOSTNAME: f"node-{i}"},
             **({} if i in bare else {ZONE: zones[i % len(zones)]})))
        for i in range(n)]


@dataclasses.dataclass(frozen=True)
class Pod(world.PodRec):
    namespace: str = "default"


def _pod(name, labels=None, ns="default", **terms):
    return Pod(name, 100, 500 << 20, 0, dict(labels or {}),
               namespace=ns, **terms)


def _sel(**kv):
    return tuple(kv.items())


def _cluster(nodes, *bound):
    c = ref.Cluster(nodes)
    for pod, i in bound:
        c.add(pod, f"node-{i}")
    return c


RED = _sel(color="red")
BLUE = _sel(color="blue")
# (what, nodes, bound [(pod, node row)...], incoming pod, feasible rows or
#  None for all, InterPodAffinity's normalised score per node)
SCORES = {
    "the pod's preferred affinity sums the pods it selects, per node": (
        _nodes(3),
        [(_pod("a", {"color": "red"}), 0), (_pod("b", {"color": "red"}), 0),
         (_pod("c", {"color": "red"}), 1), (_pod("d", {"color": "blue"}), 2)],
        _pod("in", aff_preferred=((5, HOSTNAME, RED),)),
        None, [100, 50, 0]),                  # raw 10, 5, 0
    "its preferred anti-affinity subtracts: min below 0, max stays 0": (
        _nodes(3),
        [(_pod("a", {"color": "red"}), 0), (_pod("b", {"color": "red"}), 0),
         (_pod("c", {"color": "red"}), 1)],
        _pod("in", anti_preferred=((3, HOSTNAME, RED),)),
        None, [0, 50, 100]),                  # raw -6, -3, 0
    "existing pods' preferred terms that select it count, signed": (
        _nodes(3),
        [(_pod("a", aff_preferred=((4, HOSTNAME, RED),)), 0),
         (_pod("b", anti_preferred=((2, HOSTNAME, RED),)), 1),
         (_pod("c", aff_preferred=((9, HOSTNAME, BLUE),)), 2)],
        _pod("in", {"color": "red"}),
        None, [100, 0, 33]),                  # raw 4, -2, 0: 100*(2/6) = 33
    "an existing pod's REQUIRED affinity counts hardPodAffinityWeight": (
        _nodes(4, zones=("z1", "z2")),
        [(_pod("a", {"color": "blue"}, aff_required=((ZONE, BLUE),)), 0),
         (_pod("b", {"color": "blue"}, aff_required=((ZONE, BLUE),)), 2),
         (_pod("c", {"color": "blue"}, aff_required=((ZONE, BLUE),)), 1)],
        _pod("in", {"color": "blue"}),
        None, [100, 50, 100, 50]),            # raw 2, 1, 2, 1; min from 0
    "min and max start at 0: all-positive raws are not stretched": (
        _nodes(2),
        [(_pod("a", {"color": "red"}), 0), (_pod("b", {"color": "red"}), 0),
         (_pod("c", {"color": "red"}), 0), (_pod("d", {"color": "red"}), 1)],
        _pod("in", aff_preferred=((1, HOSTNAME, RED),)),
        None, [100, 33]),                     # raw 3, 1 -> 100, 100*(1/3)
    "nothing counted: NormalizeScore is skipped, every node 0": (
        _nodes(2),
        [(_pod("a", {"color": "blue"},
               aff_preferred=((7, HOSTNAME, RED),)), 0)],
        _pod("in", {"color": "green"}, aff_preferred=((7, HOSTNAME, RED),)),
        None, [0, 0]),
    "counted but cancelling: max == min, every node 0": (
        _nodes(2),
        [(_pod("a", aff_preferred=((2, ZONE, RED),)), 0),
         (_pod("b", anti_preferred=((2, ZONE, RED),)), 1)],
        _pod("in", {"color": "red"}),
        None, [0, 0]),
    "min and max are over the feasible nodes only": (
        _nodes(3),
        [(_pod("a", {"color": "red"}), 0), (_pod("b", {"color": "red"}), 0),
         (_pod("c", {"color": "red"}), 1)],
        _pod("in", aff_preferred=((1, HOSTNAME, RED),)),
        [1, 2], [0, 100, 0]),                 # node 0 (raw 2) is out
    "a term selects within its owner's namespace only": (
        _nodes(2),
        [(_pod("a", ns="other", aff_preferred=((4, HOSTNAME, RED),)), 0),
         (_pod("b", {"color": "red"}, ns="other"), 1)],
        _pod("in", {"color": "red"}, aff_preferred=((4, HOSTNAME, RED),)),
        None, [0, 0]),
    "a selector of two labels selects a pod that carries both": (
        _nodes(2),
        [(_pod("a", {"color": "red", "tier": "db"}), 0),
         (_pod("b", {"color": "red"}), 1)],
        _pod("in", aff_preferred=((1, HOSTNAME,
                                   _sel(color="red", tier="db")),)),
        None, [100, 0]),
    "an owner on a node without the key pins nothing": (
        _nodes(3, bare=(2,)),
        [(_pod("a", aff_preferred=((6, ZONE, RED),)), 2)],
        _pod("in", {"color": "red"}),
        None, [0, 0, 0]),
    "the float64 product, as upstream computes it: 29/50 reads 57": (
        _nodes(3),
        [(_pod("a", aff_preferred=((50, HOSTNAME, RED),)), 0),
         (_pod("b", aff_preferred=((29, HOSTNAME, RED),)), 1)],
        _pod("in", {"color": "red"}),
        None, [100, 57, 0]),
}


@pytest.mark.parametrize("what", sorted(SCORES))
def test_interpod_score_against_hand_worked_upstream_cases(what):
    nodes, bound, incoming, rows, want = SCORES[what]
    c = _cluster(nodes, *bound)
    feasible = np.ones(len(nodes), bool)
    if rows is not None:
        feasible[:] = False
        feasible[rows] = True
    assert c.interpod_score(incoming, feasible).tolist() == want


GREEN = _sel(color="green")
_green = dict(labels={"color": "green"},
              anti_required=((HOSTNAME, GREEN),))
_blue = dict(labels={"color": "blue"}, aff_required=((ZONE, BLUE),))
# (what, nodes, bound, incoming pod, the rows its filter passes)
FILTERS = {
    "an existing pod's anti-affinity keeps a pod it selects off its node": (
        _nodes(3), [(_pod("g", **_green), 1)],
        _pod("in", {"color": "green"}), [0, 2]),
    "...and lets a pod it does not select through": (
        _nodes(3), [(_pod("g", **_green), 1)],
        _pod("in", {"color": "red"}), [0, 1, 2]),
    "the pod's own anti-affinity keeps it off the pods it selects": (
        _nodes(3), [(_pod("x", {"color": "green"}), 2)],
        _pod("in", {"color": "red"}, anti_required=((HOSTNAME, GREEN),)),
        [0, 1]),
    "a zone-keyed anti-affinity term shuts the owner's whole zone": (
        _nodes(4, zones=("z1", "z2")),
        [(_pod("g", {"color": "green"},
               anti_required=((ZONE, GREEN),)), 0)],
        _pod("in", {"color": "green"}), [1, 3]),
    "required affinity: only the domains that hold a selected pod": (
        _nodes(4, zones=("z1", "z2")), [(_pod("b", {"color": "blue"}), 1)],
        _pod("in", **_blue), [1, 3]),
    "required affinity: a node without the key never passes": (
        _nodes(3, bare=(2,)), [(_pod("b", {"color": "blue"}), 0)],
        _pod("in", **_blue), [0, 1]),
    "bootstrap: nothing matches anywhere and the pod matches itself": (
        _nodes(3, bare=(2,)), [(_pod("x", {"color": "red"}), 0)],
        _pod("in", **_blue), [0, 1]),
    "no bootstrap for a pod that does not match its own term": (
        _nodes(2), [],
        _pod("in", {"color": "red"}, aff_required=((ZONE, BLUE),)), []),
    "no bootstrap once a match exists anywhere": (
        _nodes(4, zones=("z1", "z2")), [(_pod("b", {"color": "blue"}), 0)],
        _pod("in", **_blue), [0, 2]),
    "1.19: an existing pod counts only if it matches ALL the terms": (
        _nodes(2),
        [(_pod("b", {"color": "blue"}), 0),
         (_pod("c", {"color": "blue", "tier": "db"}), 1)],
        _pod("in", {"color": "red"},
             aff_required=((HOSTNAME, BLUE),
                           (HOSTNAME, _sel(tier="db")))), [1]),
    "a required term of another namespace's pod does not select": (
        _nodes(2), [(_pod("g", ns="other", **_green), 0)],
        _pod("in", {"color": "green"}), [0, 1]),
}


@pytest.mark.parametrize("what", sorted(FILTERS))
def test_interpod_filter_against_hand_worked_upstream_cases(what):
    nodes, bound, incoming, want = FILTERS[what]
    c = _cluster(nodes, *bound)
    ok = c.terms_ok(incoming)
    assert np.flatnonzero(ok).tolist() == want
    assert [c.terms_ok(incoming, r) for r in range(len(nodes))] \
        == ok.tolist()


def test_the_weighted_sum_adds_interpod_to_default_plugins_scores():
    from perfbench.reference import default_plugins
    nodes = _nodes(3)
    bound = [(_pod("a", {"color": "red"}), 0)]
    incoming = _pod("in", aff_preferred=((1, HOSTNAME, RED),))
    c = _cluster(nodes, *bound)
    plain = default_plugins.Cluster(nodes)
    plain.add(_pod("a", {"color": "red"}), "node-0")
    s = c.scores(incoming)
    assert (s - plain.scores(_pod("in"))).tolist() == [100, 0, 0]
    # the busier node wins on InterPodAffinity's 100 against the resource
    # plugins' few points, as upstream's weights have it
    assert c.tie_set(incoming).tolist() == [0]
    # removing the pod takes its counts away again
    c.remove(bound[0][0])
    assert c.interpod_score(incoming, np.ones(3, bool)).tolist() == [0, 0, 0]


@pytest.mark.parametrize("field,value", [
    ("spread", ((5, ZONE, "DoNotSchedule", BLUE),)),
    ("node_affinity_in", ((ZONE, ("z",)),))])
def test_the_reference_refuses_what_it_does_not_model(field, value):
    c = ref.Cluster(_nodes(2))
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


def test_replay_holds_a_bind_to_every_bound_pods_required_terms():
    nodes = _nodes(3)
    green, blue = _pod("g", **_green), _pod("b", **_blue)
    pods = {"g2": _pod("g2", {"color": "green"}), "b2": _pod("b2", **_blue),
            "p": _pod("p")}
    log = [("add", "g2", 0.0), ("bind", "g2", "node-0", 0.1),
           ("add", "b2", 0.2), ("bind", "b2", "node-2", 0.3),
           ("add", "p", 0.4), ("bind", "p", "node-0", 0.5)]
    out = ref.replay(nodes, [(green, "node-0"), (blue, "node-1")], pods, log,
                     {"g2": "node-0", "b2": "node-2", "p": "node-0"})
    assert out == ["required (anti-)affinity violated: g2 on node-0"]


# ------------------------------------------------------ the configuration

@pytest.fixture(scope="module")
def row():
    return spec.load_json(os.path.join(spec.ROOT, "perfbench", "configs",
                                       ROW + ".json"))


def test_the_row_states_upstreams_shapes_and_cuts_nothing(row):
    world.validate(row)
    assert row["reduced"] == [] and row["chips"] == 1
    assert row["templates"] == {t: perfbench_toy.UPSTREAM_TEMPLATES[t]
                                for t in perfbench_toy.MIXED_INIT}
    assert world.init_groups(row) == [(t, 2000)
                                      for t in perfbench_toy.MIXED_INIT]
    assert row["scheduler"] == {"mode": "gang", "batch_size": 1024}
    assert row["cluster"]["node_labels"] == {ZONE: ["zone1"]}
    assert "zones" not in row["cluster"] and "warmup" not in row
    assert "unverified" in row["assumed"]["templates"]
    # the control has to fail the harness's own (plain) sample
    assert (row["reference"], row["control"]) == ("interpod_terms",
                                                  "bf16-scores")
    m = world.measured_record(row, "measured", 7)
    assert m.labels == {} and not any(
        getattr(m, f) for f in existing_terms.OWNED)
    cell = spec.cell(CELL)
    assert cell.entry["traffic"] == "saturated-d4096" and cell.chips == 1
    bench = spec.load_benchmark()
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert "auction_roofline" not in listed
    assert {"term_rows_rebuilt_per_cycle.sat",
            "terms_upload_ms_per_cycle.sat", "auction_terms_roofline",
            "term_refresh_ms_per_cycle.sat",
            "auction_device_ms_per_cycle.sat"} <= listed
    # every .sat metric the two older cells report (a later PR's own
    # metrics are its own)
    assert listed >= {m["name"] for m in bench["per_layer"]
                      if m["name"].endswith(".sat")
                      and "sp-basic-5000.saturated" in m.get("workloads", [])}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 5])
def test_the_rows_world_is_stable_and_every_green_pod_sits_alone(row, seed):
    assert world_digest.digests(world, row, seed) \
        == world_digest.digests(world, json.loads(json.dumps(row)), seed)
    init = world.init_records(row, seed)
    assert len(init) == 10000
    on = {}
    for rec, node in init:
        on.setdefault(node, []).append(rec.labels.get("color"))
    assert len(on) == 5000 and all(len(v) == 2 for v in on.values())
    assert sum(v.count("green") for v in on.values()) == 2000
    assert max(v.count("green") for v in on.values()) == 1
    # the init placement keeps every required term: the reference, fed
    # the init pods one by one, never sees one break
    cluster = ref.Cluster(world.node_records(row))
    for rec, node in init:
        assert cluster.terms_ok(rec, cluster.row[node]), (rec.name, node)
        cluster.add(rec, node)


# -------------------------- the program held to the reference, in small

def toy_cell(nodes=100, per_template=40, batch=32, resident_bound=32,
             control="bf16-scores"):
    """Upstream's row in small: the five templates as residents, exactly
    two a node as in the row (a node short of its two would draw every
    resident of check (b) and leave the rest all tied), with this row's
    reference and control (or the mixed sample's: ``no-terms-match``)."""
    config = dict(
        perfbench_toy.TOY_MIXED, name="toy-mixed-terms",
        cluster=dict(perfbench_toy.TOY_MIXED["cluster"], nodes=nodes),
        init_pods=[{"template": t, "count": per_template}
                   for t in perfbench_toy.MIXED_INIT],
        scheduler={"mode": "gang", "batch_size": batch},
        reference="interpod_terms", control=control)
    world.validate(config)
    module = spec._load_module(
        os.path.join(spec.ROOT, "perfbench", "controls", control + ".py"),
        "toy_" + control.replace("-", "_"))
    return SimpleNamespace(
        name="toy-mixed-terms.closed", config=config,
        traffic={"resident_bound": resident_bound},
        reference=lambda: ref, control=lambda: module)


SEEDS = (1, 2, 2 ** 31 + 7)


def test_the_mixed_sample_cycles_every_shape_and_strips_the_label_only():
    cell = toy_cell()
    kinds = mixed_sample_check.shapes(cell.config)
    assert kinds == [(t, True) for t in ("pod-default",) + tuple(
        perfbench_toy.MIXED_INIT[1:])] + [
        (t, False) for t in perfbench_toy.MIXED_INIT[1:]]
    sample = mixed_sample_check.mixed_sample(cell, 5)
    assert len(sample) == 32 and len({r.name for r in sample}) == 32
    for i, rec in enumerate(sample):
        template, with_terms = kinds[i % len(kinds)]
        want = world.pod_record(cell.config, template, "sample", 0)
        assert rec.labels == want.labels
        assert bool(any(getattr(rec, f) for f in existing_terms.OWNED)) \
            == (with_terms and template != "pod-default")


@pytest.mark.parametrize("seed", SEEDS)
def test_a_gang_cycle_of_the_program_lies_in_the_references_tie_sets(seed):
    """A fresh build: plain pods, upstream's four labelled shapes and the
    four labelled shapes without their terms in ONE batch, over a
    cluster whose residents carry all four kinds of term."""
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    assert mixed_sample_check.reference_misses(cell, seed, nodes, init) == 0
    assert mixed_sample_check.program_misses(cell, seed, nodes, init) == []
    # ...and the harness's own check (b), plain pods alone
    assert check.gang_check(cell, seed, nodes, init) == []


def _churned_cycle(cell, seed, nodes, bound, churn, sample):
    """Like ``check.program_gang_cycle``, but the scheduler first places
    the ``churn`` batches one cycle each and a third of what it placed is
    deleted again, so the sample's cycle runs on tables the delta path
    kept (``DeltaTensorizer._refresh_terms``), not on a fresh build.
    Returns (sample placements, [(churn pod left, its node)...], the
    records of the sample's cycles)."""
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import trace as utrace
    from kubetpu.utils.metrics import SchedulerMetrics
    store = world.build_store(nodes, bound)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    sched = Scheduler(
        store, config=world.scheduler_config(cell.config["scheduler"]),
        metrics=SchedulerMetrics(), seed=drive.scheduler_seed(seed),
        async_binding=False)

    def cycle(recs):
        for rec in recs:
            store.add(world.api_pod(rec))
        while sched.schedule_pending(timeout=0.2):
            pass
    try:
        left = {}
        for batch in churn:
            cycle(batch)
            left.update({rec.name: rec for rec in batch})
            for name in sorted(left)[::3]:
                store.delete(store.get_pod("default", name))
                del left[name]
        n_before = len(flight.cycles())
        cycle(sample)
        records = [c.to_dict() for c in flight.cycles()][n_before:]
    finally:
        sched.close()
        utrace.disarm_flight_recorder()

    def node_of(name):
        return store.get_pod("default", name).spec.node_name or ""
    return ({rec.name: node_of(rec.name) for rec in sample},
            [(rec, node_of(name)) for name, rec in left.items()], records)


@pytest.mark.parametrize("seed", SEEDS)
def test_the_same_through_the_delta_paths_term_tables(seed):
    cell = toy_cell()
    nodes = world.node_records(cell.config)
    init = world.init_records(cell.config, seed)
    cluster, bound = check.check_cluster(cell, ref, seed, nodes, init)
    churn = [[dataclasses.replace(rec, name=f"churn-{k}-{i}")
              for i, rec in enumerate(
                  mixed_sample_check.mixed_sample(cell, seed + 100 + k))]
             for k in range(3)]
    sample = mixed_sample_check.mixed_sample(cell, seed)
    placed, left, records = _churned_cycle(cell, seed, nodes, bound, churn,
                                           sample)
    # the sample's cycle refreshed the terms on the delta path: owners
    # had left since the cycle before, no resync
    first = records[0]
    assert first["meta"]["resync"] is False
    refresh = [s for s in first["spans"] if s["name"] == "delta-terms"]
    assert len(refresh) == 1 and refresh[0]["args"]["owners_changed"] > 0
    # the reference's cluster is what the store holds: the churn pods
    # that were left, where the program bound them
    assert all(node for _, node in left)
    for rec, node in left:
        cluster.add(rec, node)
    assert ref.gang_misses(cluster, sample, placed) == []


def test_the_rows_control_fails_the_harness_own_plain_sample():
    """``bf16-scores``, the row's control: on check (b)'s own sample (the
    measured template, plain) the program with its summed scores held in
    bfloat16 lands outside the tie sets, and so does the reference's
    auction with ``lowprec`` passed through to ``default_plugins``; the
    tree and the reference's float64 auction read 0."""
    cell = toy_cell()
    control = cell.control()
    assert control.REFERENCE_KW == {"lowprec": True}
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


def test_lowprec_rounds_the_interpod_part_of_the_sum_too():
    nodes = _nodes(3)
    c = _cluster(nodes, (_pod("red", {"color": "red"}, aff_preferred=(
        (1, HOSTNAME, _sel(color="red")),)), 0))
    pod = _pod("in", {"color": "red"})
    exact, low = c.scores(pod), c.scores(pod, lowprec=True)
    assert c.interpod_score(pod, c.feasible(pod)).tolist() == [100, 0, 0]
    assert exact[0] > exact[1] == exact[2]
    assert (low == ref._base.bf16(low)).all() and low[0] == low[1]


def test_the_mixed_samples_control_fails_where_existing_terms_select():
    """``no-terms-match``: on the mixed sample the label-only pods land
    where an existing pod's term forbids or dislikes; the program as it
    stands reads 0.  On the harness's plain sample no existing term
    selects anything, so the control changes nothing there: check (b) of
    this row cannot see the existing-term tables (PERF.md, section 7),
    which is why the row names another control."""
    cell = toy_cell(control="no-terms-match")
    control = cell.control()
    assert control.REFERENCE_KW == {"no_terms_match": True}
    nodes = world.node_records(cell.config)
    sound, broken, by_reference, plain = [], [], [], []
    for seed in SEEDS:
        init = world.init_records(cell.config, seed)
        sound.append(len(mixed_sample_check.program_misses(
            cell, seed, nodes, init)))
        by_reference.append(mixed_sample_check.reference_misses(
            cell, seed, nodes, init, **control.REFERENCE_KW))
        with control.program_control():
            broken.append(len(mixed_sample_check.program_misses(
                cell, seed, nodes, init)))
            plain.append(len(check.gang_check(cell, seed, nodes, init)))
    assert sound == [0, 0, 0]
    assert min(broken) >= 1 and min(by_reference) >= 1, (broken,
                                                         by_reference)
    assert plain == [0, 0, 0]


# ------------------------------------------- the count and the readers

def test_existing_terms_ops_against_a_hand_count():
    # 8,000 one-label terms x 1,024 pods x (compare + and + namespace)
    assert existing_terms.ops(1024, 8000) == 8000 * 1024 * 3
    # two labels a term: five operations a pair; 7 matched pairs reaching
    # 10 nodes each, over 3 rounds
    assert existing_terms.ops(4, 6, labels_per_term=2.0,
                              matched_node_adds=70, rounds=3) \
        == 6 * 4 * 5 + 70 * 3
    assert existing_terms.bytes_moved(8000) == 4 * 7 * 8000
    assert existing_terms.bytes_moved(6, 70, 3) == 4 * (7 * 6 + 2 * 70 * 3)


def test_the_rows_shapes_come_from_its_file_alone(row):
    shapes = existing_terms.shapes_of(row, 5000, 1024, world)
    assert shapes == {"term_rows": 8000.0, "labels_per_term": 1.0,
                      "matched_node_adds_per_pod": 0.0}
    # the anti-affinity row: every term selects every measured pod, on
    # the owner's own node
    anti = spec.load_json(os.path.join(
        spec.ROOT, "perfbench", "configs", "sp-antiaffinity-5000.json"))
    assert existing_terms.shapes_of(anti, 5000, 1024, world) == {
        "term_rows": 2024.0, "labels_per_term": 1.0,
        "matched_node_adds_per_pod": 2024.0}
    # a zone-keyed term that selects: the owner's whole zone
    toy = dict(perfbench_toy.TOY_MIXED, measured_pods={
        "template": "pod-with-pod-affinity"})
    got = existing_terms.shapes_of(toy, 96, 0, world)
    assert got["matched_node_adds_per_pod"] == 24 * 96.0
    pk = peaks.peak("TPU v5 lite")
    least = existing_terms.least_seconds(
        1024, 5000, 1, pk.flops_per_s, pk.bytes_per_s, 11024, False, 8000)
    plain = auction.least_seconds(1024, 5000, 1, pk.flops_per_s,
                                  pk.bytes_per_s, 11024, False)
    assert least["term_ops"] == 8000 * 1024 * 3
    assert least["ops_seconds"] == pytest.approx(
        plain["ops_seconds"] + 8000 * 1024 * 3 / pk.flops_per_s)


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": args}


def _recorded(refresh_args=None, upload=True, refresh=True):
    spans = [_span("tensorize", 0.0, 0.5), _span("delta-build", 0.0, 0.1)]
    if refresh:
        spans.append(_span("delta-terms", 0.1, 0.3, delta_rows=9,
                           **(refresh_args or {})))
        if upload:
            spans.append(_span("delta-terms-upload", 0.3, 0.34))
    spans.append(_span("delta-apply", 0.3, 0.4))
    return {"t0": 0.0, "spans": spans,
            "meta": {"auction_rounds": 1, "pods": 1024,
                     "term_buckets": [2048, 8192]}}


NEW_ARGS = dict(filter_rows=2000, score_rows=6000, Et=2048, Es=8192,
                pods_walked=12000, owners_changed=0)


def _read(name, cycles, **ctx):
    path = os.path.join(spec.ROOT, "perfbench", "metrics", name + ".py")
    read = spec._load_module(path, "reader_" + name.replace(".", "_")).read
    return read(SimpleNamespace(cycles=cycles, **ctx))


def test_the_two_span_readers_on_a_recorded_cycle():
    rows, upload = ("term_rows_rebuilt_per_cycle.sat",
                    "terms_upload_ms_per_cycle.sat")
    full, quiet = _recorded(NEW_ARGS), _recorded(refresh=False)
    assert _read(rows, [full]) == 8000.0
    assert _read(upload, [full]) == pytest.approx(40.0)
    # a cycle whose terms were not dirty counts as 0, not as a gap
    assert _read(rows, [full, quiet]) == 4000.0
    assert _read(upload, [full, quiet]) == pytest.approx(20.0)
    assert _read(rows, [quiet]) == 0.0 and _read(upload, [quiet]) == 0.0
    # the parent's program: the span without the args, no upload span
    old = _recorded(upload=False)
    assert _read(rows, [old]) is None and _read(upload, [old]) is None
    assert _read(rows, []) is None and _read(upload, []) is None


def test_the_roofline_reader_on_a_recorded_cycle(row):
    cell = SimpleNamespace(config=row, traffic={"resident_bound": 1024})
    trace = {"modules": {"jit__schedule_gang(1)": {"count": 2,
                                                   "seconds": 0.2}}}
    ctx = dict(cell=cell, trace=trace, device={"kind": "TPU v5 lite"},
               n_nodes=5000, resident_pods=11024)
    got = _read("auction_terms_roofline", [_recorded(NEW_ARGS)], **ctx)
    pk = peaks.peak("TPU v5 lite")
    want = (auction.ops(1024, 5000, 1) + 8000 * 1024 * 3) / pk.flops_per_s
    assert got == pytest.approx(100.0 * want / 0.1)
    assert 0 < got < 100
    # nothing to read: no auction in the trace, or no round count
    assert _read("auction_terms_roofline", [_recorded(NEW_ARGS)],
                 **dict(ctx, trace={"modules": {}})) is None
    assert _read("auction_terms_roofline", [], **ctx) is None
