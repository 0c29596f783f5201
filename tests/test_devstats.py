"""Device-side observability (kubetpu/utils/devstats.py): measured
per-program device time via sampled micro-fences, the HBM residency
ledger + capacity planner, the roofline join against
COMPILE_MANIFEST.json, the /debug/devicez endpoint, the house arming
contract (disarmed poison + armed-vs-disarmed placement parity), the
capacity-planner sanity gate (projection vs measured bytes within 10%
at bench shapes), and the monotonic-clock regression for trace spans.

Budget note: the armed/disarmed/bigger-shape drains are module-scoped
and SHARED across tests (one drain each), mirroring the consolidation
discipline the journal/replay suites adopted to keep tier-1 inside its
time budget.
"""
import json
import time
import urllib.error
import urllib.request

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.server import SchedulerServer
from kubetpu.utils import devstats as ud
from kubetpu.utils import trace as utrace
from kubetpu.utils.devstats import DevStats


def _gang_world(n_nodes, n_pods, batch, infeasible=False):
    store = ClusterStore()
    for i, n in enumerate(hollow.make_nodes(n_nodes, zones=4)):
        store.add(n)
        for p in hollow.make_pods(1, prefix=f"ex-{i}-", group_labels=8):
            p.spec.node_name = n.name
            store.add(p)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch, mode="gang",
        chain_cycles=True, pipeline_cycles=True, pipeline_depth=2),
        async_binding=False)
    for p in hollow.make_pods(n_pods, prefix="pend-", group_labels=8):
        store.add(p)
    if infeasible:
        store.add(hollow.make_pod("too-big", cpu_milli=999999))
    return store, sched


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        outs.extend(got)
    outs.extend(sched.flush_pipeline())
    return outs


def _placements(outs):
    return sorted((o.pod.metadata.name, o.node) for o in outs)


@pytest.fixture(scope="module")
def drains():
    """ONE armed pipelined gang drain (sample_interval=1: every cycle
    deep-fenced), its disarmed parity twin, and ONE armed drain at the
    doubled shape for the capacity-planner sanity gate.  Shared by the
    whole module."""
    try:
        utrace.disarm_flight_recorder()
        fr = utrace.arm_flight_recorder(capacity=32)
        ud.disarm_devstats()
        ds = ud.arm_devstats(sample_interval=1)
        store, sched = _gang_world(32, 96, 16, infeasible=True)
        # mid-drain ledger snapshot: the speculative chain is resident
        # only while a chained successor is pending — the bucket guard
        # (or a chain break) legitimately drops its entry, so capture
        # the first post-cycle ledger that carries one
        armed_outs = []
        ledger_mid = None
        for _ in range(4):
            armed_outs.extend(sched.schedule_pending(timeout=0.0))
            led = ds.ledger()
            if ledger_mid is None and any(
                    e["group"] == "chain"
                    for e in led["entries"].values()):
                ledger_mid = led
        armed_outs.extend(_drain(sched))
        armed_doc = ds.to_dict()
        pipeline_doc = fr.to_pipeline_doc(workload="devstats-test")
        spans = [s.name for rec in fr.cycles() for s in rec.spans()]
        ledger_a = ds.ledger()
        sched.close()
        utrace.disarm_flight_recorder()
        ud.disarm_devstats()

        store, sched = _gang_world(32, 96, 16, infeasible=True)
        disarmed_outs = _drain(sched)
        sched.close()

        ds2 = ud.arm_devstats(sample_interval=4)
        store, sched = _gang_world(64, 192, 32)
        _drain(sched)
        ledger_b = ds2.ledger()
        sched.close()
        return {
            "armed_outs": armed_outs, "disarmed_outs": disarmed_outs,
            "doc": armed_doc, "pipeline_doc": pipeline_doc,
            "spans": spans,
            "ledger_a": ledger_a, "ledger_mid": ledger_mid,
            "ledger_b": ledger_b,
        }
    finally:
        utrace.disarm_flight_recorder()
        ud.disarm_devstats()


# -------------------------------------------------- measured device time


def test_fence_records_per_program_device_time(drains):
    doc = drains["doc"]
    progs = doc["programs"]
    # every cycle was a deep cycle: the auction was fenced
    ra = progs["run_auction"]
    assert ra["count"] >= 1
    assert ra["device_time_s"] > 0
    assert ra["sources"].get("fence", 0) >= 1
    # the infeasible pod forced failure cycles -> the audit's natural
    # sync recorded explain_verdicts without any fence
    ev = progs["explain_verdicts"]
    assert ev["sources"].get("sync", 0) >= 1
    # sampling overhead is accounted, never invisible
    assert doc["fenced_cycles"] >= 1
    assert doc["fence_wait_s"] >= ra["device_time_s"] - 1e-9
    assert doc["sample_interval"] == 1


def test_roofline_join_on_measured_programs(drains):
    ra = drains["doc"]["programs"]["run_auction"]
    rl = ra["roofline"]
    # the gang auction pairs ANALYTIC flops (utils/flops) with the
    # fenced seconds
    assert rl["flops_source"] == "analytic"
    assert rl["achieved_tflops"] > 0
    assert rl["manifest_variant"] and rl["arithmetic_intensity"] > 0
    # this suite runs on the cpu backend, which has no peaks row: a share
    # of some OTHER part's roofline must not be reported for it
    assert not {"roofline_fraction", "regime",
                "roofline_bound_tflops"} & set(rl)
    # explain_verdicts has no analytic model: scaled from the census row
    ev = drains["doc"]["programs"]["explain_verdicts"]
    assert ev["roofline"]["flops_source"] == "scaled-census"


def test_device_fence_span_lands_on_flight_record(drains):
    assert "device-fence" in drains["spans"]


def test_pipeline_doc_carries_device_block(drains):
    dev = drains["pipeline_doc"].get("device")
    assert dev is not None
    assert dev["programs"]["run_auction"]["count"] >= 1
    assert dev["ledger_bytes"] > 0
    # ...and traceview digests it
    import tools.traceview as tv
    line = tv.device_summary(drains["pipeline_doc"])
    assert line.startswith("device: ")
    assert "run_auction" in line and "HBM resident" in line


def test_roofline_unit_math():
    costs = {"_schedule_gang": {"flops": 1e6, "bytes_accessed": 1e6,
                                "in_bytes": 1000, "variant": "t",
                                "lowering_sha256": "x"}}
    from kubetpu.utils.flops import DEVICE_PEAKS
    v5e = DEVICE_PEAKS["TPU v5 lite"]
    rl = ud.roofline("run_auction", 0.001, flops=1e6, costs=costs,
                     peaks=v5e)
    # AI = 1 flop/byte -> memory-bound on any realistic part
    assert rl["regime"] == "memory-bound"
    bound = rl["roofline_bound_tflops"] * 1e12
    assert bound == pytest.approx(1.0 * v5e.hbm_bytes_per_s)
    assert rl["achieved_tflops"] == pytest.approx(1e6 / 0.001 / 1e12)
    assert rl["roofline_fraction"] == pytest.approx(1e9 / bound)
    # scaled-census fallback: flops scale by operand bytes
    rl2 = ud.roofline("run_auction", 0.001, in_bytes=2000, costs=costs)
    assert rl2["flops_source"] == "scaled-census"
    assert rl2["achieved_tflops"] == pytest.approx(2e6 / 0.001 / 1e12)
    # unknown program: no join, never an error
    assert ud.roofline("no_such_program", 0.1, flops=1.0) is None


def test_manifest_costs_and_aval_parsing():
    costs = ud.manifest_costs()
    for prog in ud.PROGRAMS.values():
        assert prog in costs, prog
        row = costs[prog]
        assert row["flops"] > 0 and row["bytes_accessed"] > 0
        assert row["in_bytes"] > 0
    assert ud._aval_bytes("float32[64,12]") == 4 * 64 * 12
    assert ud._aval_bytes("bool[8]") == 8
    assert ud._aval_bytes("garbage") == 0


# -------------------------------------------------------- residency ledger


def test_ledger_registers_resident_and_chain(drains):
    entries = drains["ledger_a"]["entries"]
    resident = entries["delta-resident/default-scheduler"]
    assert resident["bytes"] > 0
    assert resident["axes"]["nodes"] == 32
    assert resident["axes"]["pods"] >= 96          # pow2 pod bucket
    assert "allocatable" in resident["tables"]
    assert "pod_kv" in resident["tables"]
    # the speculative chain is a second resident cluster while chained
    # cycles are live (mid-drain snapshot — the bucket guard and chain
    # breaks legitimately drop the entry between registrations, the
    # lifecycle drop_group now implements)
    assert drains["ledger_mid"] is not None, \
        "no cycle ever registered a chain residency"
    chain = drains["ledger_mid"]["entries"].get("chain/default-scheduler")
    assert chain is not None and chain["bytes"] > 0


def test_projection_identity_is_exact(drains):
    led = drains["ledger_a"]
    ent = led["entries"]["delta-resident/default-scheduler"]
    proj = ud.project(led, ent["axes"]["nodes"], ent["axes"]["pods"],
                      groups=("delta-resident",))
    assert proj["total_bytes"] == ent["bytes"]


def test_capacity_planner_sanity_gate_within_10pct(drains):
    """THE acceptance gate: project the small bench-shape ledger to the
    doubled shape and compare against the bytes the doubled drain
    ACTUALLY registered — the north-star projection is only trustworthy
    if this holds."""
    led_a, led_b = drains["ledger_a"], drains["ledger_b"]
    ent_b = led_b["entries"]["delta-resident/default-scheduler"]
    measured = ent_b["bytes"]
    # committed pods at shape B: 64 existing + 192 pending
    proj = ud.project(led_a, 64, 64 + 192, groups=("delta-resident",))
    rel = abs(proj["total_bytes"] - measured) / measured
    assert rel <= 0.10, (proj["total_bytes"], measured)


def test_northstar_projection_answers_fit(drains):
    from kubetpu.utils.flops import DEVICE_PEAKS
    proj = ud.project(drains["ledger_a"], 10000, 100000, shards=8,
                      groups=("delta-resident", "chain"),
                      peaks=DEVICE_PEAKS["TPU v5 lite"])
    assert proj["pod_bucket"] == 131072
    assert proj["total_bytes"] > 0
    assert proj["per_shard_bytes"] < proj["total_bytes"]
    assert isinstance(proj["fits_single_chip"], bool)
    assert isinstance(proj["fits_per_shard"], bool)
    # per-table attribution exists (pod_kv is the known dominator)
    assert any(k.endswith("/pod_kv") for k in proj["per_table_bytes"])


def test_devplan_cli_and_ledger_discovery(tmp_path, drains):
    import tools.devplan as dp
    # find_ledger resolves every supported document shape
    raw = drains["ledger_a"]
    assert dp.find_ledger(raw) is raw
    assert dp.find_ledger({"ledger": raw}) is raw                 # devicez
    assert dp.find_ledger(
        {"detail": {"device_ledger": raw}}) is raw   # committed bench JSON
    assert dp.find_ledger(
        {"headline": {}, "detail": {"device_ledger": raw}}) is raw
    assert dp.find_ledger({"nope": 1}) is None
    path = tmp_path / "devicez.json"
    path.write_text(json.dumps({"ledger": raw}))
    # fits at its own shape -> exit 0
    assert dp.main([str(path), "--nodes", "32", "--pods", "128"]) == 0
    # unusable input -> exit 1
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert dp.main([str(bad), "--nodes", "1", "--pods", "1"]) == 1


def test_record_bytes_replaces_by_name():
    ds = DevStats(sample_interval=4)
    ds.record_bytes("aot-executables", "", "row-a", 1000)
    ds.record_bytes("aot-executables", "", "row-b", 500)
    # re-loading the SAME artifact (fresh runtime, bench attempt) must
    # not double-count residency: registration replaces by name
    ds.record_bytes("aot-executables", "", "row-a", 1200)
    led = ds.ledger()
    ent = led["entries"]["aot-executables"]
    assert ent["bytes"] == 1700 and ent["registrations"] == 3
    # opaque byte entries pass through projection unscaled
    proj = ud.project(led, 99999, 999999)
    assert proj["total_bytes"] == 1700


def test_drop_group_unregisters_chain_residency():
    """The ledger describes what is resident NOW: a discarded chain's
    entry must stop counting against the capacity projection."""
    ds = DevStats(sample_interval=4)
    ds.record_bytes("chain", "p", "cluster", 4096)
    ds.record_bytes("delta-resident", "p", "cluster", 1024)
    assert ds.has_group("chain")
    ds.drop_group("chain")
    assert not ds.has_group("chain")
    led = ds.ledger()
    assert "chain/p" not in led["entries"]
    assert led["total_bytes"] == 1024


def test_dim_tags_survive_node_pod_collision():
    """A world whose node count EQUALS its pod bucket must still
    project the pod axis through pow2_bucket and the node axis
    linearly — the registration-time dim tags disambiguate what value
    matching cannot."""
    entries = {
        "pod_kv": [{"shape": [256, 512], "dtype": "bool",
                    "bytes": 256 * 512}],
        "allocatable": [{"shape": [256, 12], "dtype": "float32",
                         "bytes": 256 * 12 * 4}],
        "image_size": [{"shape": [256], "dtype": "float32",
                        "bytes": 256 * 4}],
    }
    axes = {"nodes": 256, "pods": 256, "kv": 512}
    ud._tag_cluster_dims(entries, axes)
    assert entries["pod_kv"][0]["dims"][0] == "pods"
    assert entries["allocatable"][0]["dims"][0] == "nodes"
    # vocab-side [I] table: dim 0 is NOT the node axis despite the
    # coincidental size match
    assert entries["image_size"][0]["dims"][0] is None
    led = {"entries": {"delta-resident/p": {
        "group": "delta-resident", "profile": "p", "axes": axes,
        "tables": entries, "bytes": 0, "meta": {}, "registrations": 1}}}
    # nodes x2, pods -> 100k (bucket 131072 = x512 on the pod axis)
    proj = ud.project(led, 512, 100000)
    tb = proj["per_table_bytes"]
    kv_scale = 1024 / 512       # kv follows nodes linearly, re-bucketed
    assert tb["delta-resident/p/pod_kv"] == int(
        256 * 512 * (131072 / 256) * kv_scale)
    assert tb["delta-resident/p/allocatable"] == 256 * 12 * 4 * 2
    assert tb["delta-resident/p/image_size"] == 256 * 4   # held


# ------------------------------------------------------- house contract


def test_armed_vs_disarmed_placements_bit_identical(drains):
    armed = _placements(drains["armed_outs"])
    disarmed = _placements(drains["disarmed_outs"])
    assert armed == disarmed
    assert sum(1 for _, node in armed if node) == 96


def test_disarmed_hot_path_is_noop(monkeypatch):
    """Disarmed, a full pipelined gang drain (with failure cycles) must
    never construct a DevStats, tick a cycle, record a program, walk a
    ledger registration, or compute operand bytes — the zero-new-locks
    contract, same poison pattern as tests/test_slo.py."""
    ud.disarm_devstats()

    def boom(*a, **kw):
        raise AssertionError("hot path touched disarmed devstats")

    monkeypatch.setattr(ud.DevStats, "__init__", boom)
    monkeypatch.setattr(ud.DevStats, "begin_cycle", boom)
    monkeypatch.setattr(ud.DevStats, "deep_active", boom)
    monkeypatch.setattr(ud.DevStats, "record_program", boom)
    monkeypatch.setattr(ud.DevStats, "record_ledger", boom)
    monkeypatch.setattr(ud.DevStats, "record_bytes", boom)
    monkeypatch.setattr(ud, "register_cluster", boom)
    monkeypatch.setattr(ud, "table_entries", boom)
    monkeypatch.setattr(ud, "pytree_nbytes", boom)

    store, sched = _gang_world(4, 12, 8, infeasible=True)
    try:
        outs = _drain(sched)
        assert sum(1 for o in outs if o.node) == 12
    finally:
        sched.close()


# ------------------------------------------------------------------- HTTP


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_debug_devicez_roundtrip():
    ud.disarm_devstats()
    ds = ud.arm_devstats(sample_interval=1)
    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8),
        async_binding=False)
    for p in hollow.make_pods(6):
        store.add(p)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        _drain(sched)
        code, doc = _get(port, "/debug/devicez")
        assert code == 200 and doc["armed"] is True
        assert doc["programs"]["schedule_sequential"]["count"] >= 1
        assert doc["ledger"]["total_bytes"] > 0
        assert "fence_wait_s" in doc
        code, doc = _get(port,
                         "/debug/devicez?program=schedule_sequential")
        assert code == 200
        assert set(doc["programs"]) == {"schedule_sequential"}
        code, doc = _get(port, "/debug/devicez?program=nope")
        assert code == 400 and "unknown program" in doc["error"]
    finally:
        srv.stop()
        sched.close()
        ud.disarm_devstats()


def test_debug_devicez_disarmed_404():
    ud.disarm_devstats()
    store = ClusterStore()
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()]), async_binding=False)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        code, doc = _get(port, "/debug/devicez")
        assert code == 404 and doc["armed"] is False
    finally:
        srv.stop()
        sched.close()


# ----------------------------------------------------------------- xplane


def test_xplane_ingest_records_reason_when_unavailable(tmp_path):
    ds = DevStats(sample_interval=4)
    # no capture at all
    st = ds.ingest_xplane(str(tmp_path))
    assert st["available"] is False and "no .xplane.pb" in st["reason"]
    # a capture exists but the profiler tooling is not importable in the
    # serving image: the reason is recorded, never silently dropped
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(b"\x00fake")
    st = ds.ingest_xplane(str(tmp_path))
    assert st["captures"] == 1
    if not st["available"]:
        assert "reason" in st
    assert ds.to_dict()["xplane"]["captures"] == 1


# ------------------------------------------------- benchtrend attribution


def test_benchtrend_device_attribution():
    from tools.benchtrend import attribute_regression, device_attribution
    prev = {"latency": {"stage_shares": {"device": 0.5, "bind": 0.5}},
            "device": {"ledger_bytes": 1000, "programs": {
                "run_auction": {"mean_s": 0.01,
                                "roofline_fraction": 0.4}}}}
    cur = {"latency": {"stage_shares": {"device": 0.7, "bind": 0.3}},
           "device": {"ledger_bytes": 2000, "programs": {
               "run_auction": {"mean_s": 0.02,
                               "roofline_fraction": 0.1}}}}
    note = attribute_regression(prev, cur)
    assert "stage 'device' share grew" in note
    assert "run_auction" in note and "achieved fraction fell" in note
    assert "resident HBM grew" in note
    # no device block on either side: attribution degrades silently
    assert device_attribution({}, {}) == ""
    # no roofline join: falls back to the mean device time growing
    p2 = {"device": {"programs": {"x": {"mean_s": 0.01}}}}
    c2 = {"device": {"programs": {"x": {"mean_s": 0.05}}}}
    assert "device time grew" in device_attribution(p2, c2)


# -------------------------------------------------- monotonic clock fix


def test_trace_spans_survive_backwards_wall_clock(monkeypatch):
    """The satellite regression: an NTP step that moves time.time()
    BACKWARDS mid-cycle must not produce negative span durations —
    span stamps read trace.wallclock() (perf_counter anchored to the
    import-time wall epoch), which time.time() cannot move."""
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=4)
    try:
        stepped = {"n": 0}
        real_time = time.time

        def ntp_step_backwards():
            stepped["n"] += 1
            return real_time() - 3600.0 * stepped["n"]

        monkeypatch.setattr(time, "time", ntp_step_backwards)
        tr = utrace.Trace("Scheduling", profile="p", pods=1)
        tr.step("first step done")
        with tr.stage("dispatch") as sp:
            assert sp is not None
        tr.step("second step done")
        assert tr.total() >= 0.0
        tr.finish()
        recs = fr.cycles()
        assert recs, "cycle record must commit"
        rec = recs[-1]
        assert rec.t1 is not None and rec.t1 >= rec.t0
        spans = rec.spans()
        assert spans
        for s in spans:
            assert s.t1 is not None and s.t1 >= s.t0, s.name
    finally:
        utrace.disarm_flight_recorder()


def test_wallclock_monotonic_and_wall_anchored():
    a = utrace.wallclock()
    b = utrace.wallclock()
    assert b >= a
    # anchored to the wall epoch: agrees with time.time() closely on a
    # box whose clock has not stepped since import
    assert abs(utrace.wallclock() - time.time()) < 5.0
