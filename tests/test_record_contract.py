"""The program's half of the benchmark's record contract.

The benchmark's per-layer readers (perfbench/lib/readers.py, lib/spans.py,
lib/threads.py, perfbench/metrics/*.py) take names off the flight
recorder's cycle records (``CycleRecord.to_dict()``): meta keys, span
names, span args, the bind table, the ``xla-compile`` event.  A reader
that does not find its name returns None, and the ledger then shows
``null`` under ``per_layer``.  CONTRACT below lists every such name with
the type its reader expects and the metric that reads it -- copied from
a reading of perfbench/, which this file neither imports nor edits --
and ONE toy serving run on the CPU, armed as the benchmark arms it
(recorder, compile watchdog and timer, ``Scheduler.run()``, async
binding), has to carry each.
"""

import time

import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import sanitize
from kubetpu.utils import trace as utrace
from kubetpu.utils.metrics import SchedulerMetrics

PHASES = ("pop", "snapshot", "prefilter", "tensorize", "host-masks",
          "dispatch", "packed-readback", "commit")
NUMBER = (int, float)

# (kind, name, type, the metric or reader that takes it)
#   meta:  cycle["meta"][name]
#   span:  a span of that name (its t0, t1, args, thread, parent)
#   arg:   "<span>.<arg>": that span's args[arg]
#   event: "<event>.<arg>": that instant event's args[arg]
CONTRACT = [
    ("meta", "auction_rounds", int, "auction_rounds_per_cycle.sat"),
    ("meta", "pods", int, "auction_roofline (readers.auction_roofline_pct)"),
    ("meta", "pod_bucket", int, "pod_axis_rows.sat"),
    ("meta", "pod_rows_live", int, "pod_axis_live_pct.sat"),
    ("meta", "cluster_device_bytes", int, "cluster_device_mb.sat"),
    ("meta", "delta_buckets", list, "delta_apply_roofline"),
    ("meta", "delta_rows", int, "delta_apply_roofline"),
    ("meta", "term_sets_live", list, "auction_term_sets_live_per_cycle.sat"),
    ("meta", "capacity_deferred", int, "capacity_deferred_per_cycle.sat"),
    ("meta", "score_terms_spliced", int, "score_terms_spliced_per_cycle.sat"),
    ("meta", "spread_constraints", int, "spread_constraints_per_cycle.sat"),
    ("meta", "spread_soft_constraints", int,
     "soft_spread_constraints_per_cycle.sat"),
    ("meta", "spread_soft_skew", int, "soft_spread_zone_skew_per_cycle.sat"),
    ("meta", "spread_late_admits", int, "spread_late_admits_per_cycle.sat"),
    ("meta", "required_affinity_terms", int,
     "affinity_bootstrap_pods_per_cycle.sat"),
    ("meta", "affinity_bootstrap_admits", int,
     "affinity_bootstrap_pods_per_cycle.sat"),
    ("meta", "node_affinity_terms", int,
     "node_affinity_terms_per_cycle.sat (the span arg's twin)"),
    ("meta", "node_affinity_unique_selectors", int,
     "node_affinity_unique_selectors_per_cycle.sat"),
    ("meta", "heap_handoffs", int, "heap_handoffs_per_cycle.sat"),
    ("meta", "thread_cpu_s", dict, "python_cpu_ms_per_cycle.sat"),
    ("meta", "thread_cpu_window_s", NUMBER,
     "threads.thread_cpu_ms_by_name (interp_report)"),
    ("meta", "gc_collections", int, "gc_pause_ms_per_cycle.sat"),
    ("meta", "pod_classes", int, "batch_rows_shared_pct.sat (its K)"),
    ("meta", "rows_built", int, "batch_rows_shared_pct.sat"),
] + [
    ("arg", f"{phase}.cpu_s", NUMBER,
     f"{phase.replace('-', '_')}_ms_per_cycle.sat / spans.blocked_pct")
    for phase in PHASES
] + [
    ("span", "Tensorizing snapshot and pod batch done", None,
     "prepare_ms_per_cycle.sat"),
    ("span", "batch-build", None, "batch_build_ms_per_cycle.sat"),
    ("span", "delta-build", None, "delta_build_ms_per_cycle.sat"),
    ("span", "delta-apply", None, "delta_apply_ms_per_cycle.sat"),
    ("span", "row-maps", None, "tensorize_row_maps_ms_per_cycle.sat"),
    ("span", "delta-terms", None, "term_refresh_ms_per_cycle.sat"),
    ("span", "delta-terms-upload", None, "terms_upload_ms_per_cycle.sat"),
    ("span", "bind-job", None, "lane_blocked_pct.sat"),
    ("arg", "batch-build.ra_rows", int,
     "required_affinity_terms_per_cycle.sat"),
    ("arg", "batch-build.rna_rows", int,
     "node_affinity_terms_per_cycle.sat"),
    ("arg", "batch-build.rna_unique", int,
     "node_affinity_unique_selectors_per_cycle.sat (the meta's twin)"),
    ("span", "classify", None, "classify_ms_per_cycle.sat"),
    ("arg", "batch-build.pods", int, "batch_rows_shared_pct.sat"),
    ("arg", "batch-build.pod_classes", int,
     "batch_rows_shared_pct.sat (its K)"),
    ("arg", "batch-build.rows_built", int, "batch_rows_shared_pct.sat"),
    ("arg", "packed-readback.device_wait_s", NUMBER,
     "readback_wait_ms_per_cycle.sat"),
    ("arg", "commit.assume_s", NUMBER, "commit_assume_ms_per_cycle.sat"),
    ("arg", "commit.reserve_s", NUMBER, "commit_plugins_ms_per_cycle.sat"),
    ("arg", "commit.recheck_s", NUMBER, "commit_plugins_ms_per_cycle.sat"),
    ("arg", "commit.permit_s", NUMBER, "commit_plugins_ms_per_cycle.sat"),
    ("arg", "commit.submit_s", NUMBER, "commit_submit_ms_per_cycle.sat"),
    ("arg", "commit.handover_wait_s", NUMBER,
     "handover_wait_ms_per_cycle.sat"),
    ("arg", "commit.pods", int, "commit_batched_pct.sat"),
    ("arg", "commit.batched", int, "commit_batched_pct.sat"),
    ("arg", "pop.wait_s", NUMBER, "queue_empty_wait_ms_per_cycle.sat"),
    ("arg", "pop.teardown_s", NUMBER, "pop_teardown_ms_per_cycle.sat"),
    ("arg", "snapshot.pods_copied", int,
     "snapshot_pods_copied_per_cycle.sat"),
    ("arg", "delta-build.pods_walked", int,
     "delta_pods_walked_per_cycle.sat"),
    ("arg", "delta-build.pod_rows_refilled", int,
     "mirror_rows_refilled_per_cycle.sat"),
    ("arg", "delta-build.node_rows_refilled", int,
     "mirror_rows_refilled_per_cycle.sat"),
    ("arg", "delta-build.node_rows_dirty", int, "delta_apply_roofline"),
    ("arg", "delta-terms.filter_rows", int,
     "term_rows_rebuilt_per_cycle.sat"),
    ("arg", "delta-terms.score_rows", int,
     "term_rows_rebuilt_per_cycle.sat"),
    ("arg", "delta-terms.rows_written", int,
     "term_rows_written_per_cycle.sat"),
    ("arg", "bind-job.cpu_s", NUMBER, "lane_cpu_ms_per_cycle.sat"),
    ("arg", "bind-job.batched", int, "lane_batched_pct.sat"),
    ("event", "xla-compile.seconds", NUMBER, "window_compile_stall_ms.sat"),
    # PR 51: the inside of ``pop``
    ("span", "teardown", None, "teardown_report (its extent = teardown_s)"),
    ("arg", "teardown.cpu_s", NUMBER,
     "teardown_serving_cpu_ms_per_cycle.sat"),
    ("arg", "teardown.thread_cpu_s", dict,
     "teardown_lane_cpu_ms_per_cycle.sat / "
     "teardown_other_threads_cpu_ms_per_cycle.sat"),
    ("arg", "teardown.read_s", NUMBER,
     "teardown_report (the second thread-clock reading's cost)"),
    ("span", "teardown-release", None, "teardown_release_ms_per_cycle.sat"),
    ("arg", "teardown-release.cpu_s", NUMBER, "teardown_report"),
    ("arg", "teardown-release.outcomes", int, "teardown_report"),
    ("span", "heap-boundary", None, "heap_boundary_ms_per_cycle.sat"),
    ("arg", "heap-boundary.cpu_s", NUMBER, "teardown_report"),
    ("arg", "heap-boundary.handoff", int, "teardown_report"),
    ("arg", "heap-boundary.sweep", int, "teardown_report"),
    ("arg", "pop.queue_s", NUMBER, "pop_queue_ms_per_cycle.sat"),
    ("arg", "pop.group_s", NUMBER, "pop_group_ms_per_cycle.sat"),
]


def _pod(name, color=None):
    return hollow.make_pod(name, labels={"color": color} if color else {})


def _prefer(pod):
    """One preferred hostname affinity to the pod's own labels: the term
    the auction splices into its score tables (score_terms_spliced)."""
    term = api.PodAffinityTerm(
        label_selector=api.LabelSelector(
            match_labels=dict(pod.metadata.labels)),
        topology_key=api.LABEL_HOSTNAME)
    pod.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            api.WeightedPodAffinityTerm(weight=1, pod_affinity_term=term)]))
    return pod


def _in_zones(pod, *zones):
    """One required node-affinity term, ``In`` over the zone label."""
    pod.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
        required_during_scheduling_ignored_during_execution=(
            api.NodeSelector(node_selector_terms=[api.NodeSelectorTerm(
                match_expressions=[api.NodeSelectorRequirement(
                    key=api.LABEL_ZONE, operator="In",
                    values=list(zones))])]))))
    return pod


def _waves():
    """Arrivals a wave at a time; every wave is bound before the next
    arrives, so later cycles refresh the resident cluster by delta."""
    return [
        [hollow.with_spread(_pod(f"hard-{i}", "blue"), api.LABEL_ZONE,
                            max_skew=2) for i in range(10)]
        + [_pod(f"plain-{i}") for i in range(4)],
        [hollow.with_spread(_pod(f"soft-{i}", "red"), api.LABEL_ZONE,
                            when="ScheduleAnyway") for i in range(8)]
        + [_prefer(_pod(f"pref-{i}", "yellow")) for i in range(4)],
        [hollow.with_anti_affinity(_pod(f"anti-{i}", "green"),
                                   api.LABEL_HOSTNAME) for i in range(8)],
        # more than two batches: cycles that follow one another at once
        [_pod(f"burst-{i}") for i in range(40)],
        [hollow.with_anti_affinity(_pod(f"anti2-{i}", "green"),
                                   api.LABEL_HOSTNAME) for i in range(4)],
        # a required zone affinity to the pod's own label: the auction
        # says how many came in by the self-match bootstrap
        [hollow.with_affinity(_pod(f"aff-{i}", "purple"), api.LABEL_ZONE)
         for i in range(4)],
        # a required node-affinity term, the same on every pod: the
        # batch says how many rows carry one and how many it compiled
        [_in_zones(_pod(f"zoned-{i}"), "zone-0", "zone-1")
         for i in range(4)],
        [_pod(f"late-{i}") for i in range(6)],
    ]


def _await_bound(store, pods, timeout=300.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(store.get_pod("default", p.metadata.name).spec.node_name
               for p in pods):
            return
        time.sleep(0.02)
    raise AssertionError("toy run: pods left unbound")


@pytest.fixture(scope="module")
def cycles():
    """The toy run's cycle records, as the benchmark's readers get them."""
    store = ClusterStore()
    for n in hollow.make_nodes(24, zones=3):
        store.add(n)
    for i, p in enumerate(hollow.make_pods(24, prefix="init-",
                                           group_labels=4)):
        p.spec.node_name = f"node-{i}"
        store.add(p)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=256, max_spans_per_cycle=64)
    sanitize.install_compile_timer()
    watchdog = sanitize.install_compile_watchdog()
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], mode="gang", batch_size=16,
        prewarm=False), metrics=SchedulerMetrics(), seed=7,
        async_binding=True)
    try:
        sched.run()
        for wave in _waves():
            for p in wave:
                store.add(p)
            _await_bound(store, wave)
            sched.wait_for_inflight_binds()
        records = [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()
        sanitize.uninstall_compile_watchdog(watchdog)
        utrace.disarm_flight_recorder()
    assert not sched.recovery_log
    return records


def _values(cycles, kind, name):
    if kind == "meta":
        return [c["meta"][name] for c in cycles if name in c["meta"]]
    if kind == "span":
        return [s for c in cycles for s in c["spans"] if s["name"] == name]
    owner, arg = name.rsplit(".", 1)
    rows = "spans" if kind == "arg" else "events"
    return [r["args"][arg] for c in cycles for r in c[rows]
            if r["name"] == owner and arg in r["args"]]


@pytest.mark.parametrize(
    "kind,name,typ,reader", CONTRACT,
    ids=[f"{kind}:{name.replace(' ', '_')}" for kind, name, _, _ in CONTRACT])
def test_a_cycle_record_carries_what_the_benchmark_reads(
        cycles, kind, name, typ, reader):
    got = _values(cycles, kind, name)
    assert got, f"no cycle of the toy run carries {kind} {name!r} ({reader})"
    if kind == "span":
        for s in got:
            assert isinstance(s["t0"], float) and isinstance(s["t1"], float)
            assert s["t1"] >= s["t0"]
            assert isinstance(s["args"], dict)
            assert isinstance(s["thread"], str)
            assert isinstance(s["parent"], int)
        return
    for v in got:
        assert isinstance(v, typ) and not isinstance(v, bool), (name, v)
    if name.endswith("thread_cpu_s"):     # the meta's, and the teardown's
        for v in got:
            assert all(isinstance(k, str) and isinstance(x, NUMBER)
                       for k, x in v.items())
        # over a whole period somebody ran (a toy teardown can be too
        # short for any thread to pass the 0.1 ms a name takes)
        assert all(got) or name != "thread_cpu_s"


def test_a_batch_of_one_node_affinity_term_counts_its_rows_and_one_selector(
        cycles):
    """The four ``zoned-`` pods share one term: four valid rows, ONE
    unique compiled selector; every other cycle reads 0 and 0."""
    said = [(c["meta"]["node_affinity_terms"],
             c["meta"]["node_affinity_unique_selectors"])
            for c in cycles if "node_affinity_terms" in c["meta"]]
    assert sum(n for n, _ in said) == 4
    assert {u for n, u in said if n} == {1}
    assert all(u == 0 for n, u in said if not n)
    for c in cycles:
        for s in c["spans"]:
            if s["name"] == "batch-build":
                assert (s["args"]["rna_rows"], s["args"]["rna_unique"]) == (
                    c["meta"]["node_affinity_terms"],
                    c["meta"]["node_affinity_unique_selectors"])


def test_every_cycle_has_one_root_and_the_eight_phases(cycles):
    """threads.serving_thread takes the root span's thread; the phase
    readers take a cycle's phases by name."""
    assert len(cycles) >= len(_waves())
    for c in cycles:
        assert isinstance(c["t0"], float)       # prepare_ms_per_cycle
        roots = [s for s in c["spans"] if s["parent"] == 0]
        assert len(roots) == 1 and roots[0]["thread"]
        names = {s["name"] for s in c["spans"]}
        assert set(PHASES) <= names, set(PHASES) - names


def test_the_bind_table_has_a_row_a_pod_stamped_by_the_lane(cycles):
    """spans._bind_rows / lane_busy_ms_per_cycle: [submitted, started,
    done, thread] on the program's wallclock(), the lane's rows under
    the lane's thread name; admits a round (auction_admits_per_round.sat)
    counts the rows with a ``submitted``."""
    rows = [row for c in cycles for row in c["binds"]]
    assert len(rows) == sum(len(w) for w in _waves())
    for sub, start, done, thread in rows:
        assert 0.0 < sub <= start <= done
        assert thread == "binder-lane"
