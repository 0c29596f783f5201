"""Sustained-load telemetry plane (kubetpu/utils/telemetry.py) and the
open-loop harness (kubetpu/harness/hollow.py streams +
harness/perf.py SustainedLoadRunner): window-delta exactness vs numpy,
ring bounds + drop counting, the disarmed zero-cost poison contract,
the armed-vs-disarmed placement parity golden, chaos-storm attribution
to the firing window, the /debug/loadz endpoint, the /metrics window
series, and a seconds-scale open-loop smoke (the minutes soak is
``slow``-marked)."""
import json
import math
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.harness.perf import SustainedLoadRunner
from kubetpu.scheduler import Scheduler
from kubetpu.server import SchedulerServer
from kubetpu.utils import chaos
from kubetpu.utils import slo as uslo
from kubetpu.utils import telemetry as utelemetry
from kubetpu.utils.metrics import SchedulerMetrics
from kubetpu.utils.slo import BUCKET_EDGES, BUCKET_RATIO, QuantileSketch
from kubetpu.utils.telemetry import (TelemetryRing, quantile_from_counts,
                                     steady_state_span)


@pytest.fixture
def slo():
    uslo.disarm_slo_tracker()
    trk = uslo.arm_slo_tracker()
    try:
        yield trk
    finally:
        uslo.disarm_slo_tracker()


@pytest.fixture
def tel():
    """Armed ring with a giant window: rolls happen only via
    force_roll, so tests control window boundaries deterministically."""
    utelemetry.disarm_telemetry()
    ring = utelemetry.arm_telemetry(window_s=3600.0, capacity=64)
    try:
        yield ring
    finally:
        utelemetry.disarm_telemetry()


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        outs.extend(got)
    return outs


def _world(n_nodes=2, n_pods=6, batch=8, metrics=None):
    store = ClusterStore()
    for n in hollow.make_nodes(n_nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch),
        async_binding=False, metrics=metrics)
    for p in hollow.make_pods(n_pods):
        store.add(p)
    return store, sched


# ------------------------------------------------- window-delta exactness


def test_quantile_from_counts_matches_order_statistic():
    """Property: on randomized draws binned onto the shared slo ladder,
    quantile_from_counts returns the bucket upper edge of the targeted
    order statistic — never below the exact value, never more than one
    bucket ratio above it."""
    rng = np.random.default_rng(7)
    for scale in (5e-3, 0.2, 4.0):
        draws = np.sort(rng.lognormal(math.log(scale), 1.0, size=1500))
        sk = QuantileSketch()
        for v in draws:
            sk.observe(float(v))
        n = len(draws)
        for q in (0.5, 0.9, 0.99):
            est = quantile_from_counts(sk.counts, q)
            exact = float(draws[min(max(math.ceil(q * n), 1), n) - 1])
            assert exact <= est * (1 + 1e-9)
            assert est <= exact * BUCKET_RATIO * (1 + 1e-9)


def test_window_delta_isolates_each_window(slo, tel):
    """Two windows with DIFFERENT latency populations: each window's
    quantiles must describe only its own observations (the cumulative-
    minus-previous subtraction), and the merged steady quantile over
    both windows must equal the quantile of the union — exact, not a
    quantile of quantiles."""
    rng = np.random.default_rng(1)
    slow_draws = list(rng.uniform(2.0, 4.0, size=40))
    fast_draws = list(rng.uniform(0.01, 0.02, size=160))
    for v in slow_draws:
        slo.observe_pod({"e2e": v, "bind": v / 10}, pod="a", uid="a")
    tel.force_roll(None)
    for v in fast_draws:
        slo.observe_pod({"e2e": v, "bind": v / 10}, pod="b", uid="b")
    tel.force_roll(None)

    w1, w2 = tel.windows()[-2:]
    assert w1["stages"]["e2e"]["count"] == 40
    assert w2["stages"]["e2e"]["count"] == 160
    # window 2's p99 reflects ONLY the fast population — no cumulative
    # pollution from window 1's slow pods
    assert w2["stages"]["e2e"]["p99_s"] <= 0.02 * BUCKET_RATIO * 1.001
    assert w1["stages"]["e2e"]["p50_s"] >= 2.0

    # merged steady quantile == exact quantile of the union
    union = sorted(slow_draws + fast_draws)
    n = len(union)
    start = len(tel.windows()) - 2
    merged_p99 = tel.steady_quantile(start, 2, 0.99)
    exact = union[min(max(math.ceil(0.99 * n), 1), n) - 1]
    assert exact <= merged_p99 * (1 + 1e-9)
    assert merged_p99 <= exact * BUCKET_RATIO * (1 + 1e-9)


def test_delta_survives_midwindow_clear(slo, tel):
    """slo.clear() mid-window makes the cumulative counts go BACKWARD;
    the delta must clamp at zero, never go negative or crash."""
    for _ in range(10):
        slo.observe_pod({"e2e": 1.0}, pod="x", uid="x")
    tel.force_roll(None)
    slo.clear()
    slo.observe_pod({"e2e": 0.5}, pod="y", uid="y")
    w = tel.force_roll(None)
    assert w["stages"]["e2e"]["count"] >= 0
    assert w["pods"] >= 0


# ------------------------------------------------------- ring mechanics


def test_ring_wrap_and_drop_counting():
    ring = TelemetryRing(window_s=3600.0, capacity=4)
    for _ in range(7):
        ring.force_roll(None)
    wins = ring.windows()
    assert len(wins) == 4
    assert ring.dropped() == 3
    # seq keeps counting across drops — the newest 4 survive
    assert [w["seq"] for w in wins] == [4, 5, 6, 7]
    d = ring.to_dict()
    assert d["digest"]["dropped"] == 3
    assert len(d["windows"]) == 4


def test_steady_state_span_cuts_warmup():
    warm = [5.0, 3.0, 1.1, 1.0, 1.05, 1.0, 1.02, 0.98, 1.0]
    span = steady_state_span(warm)
    assert span is not None
    start, n = span
    assert start >= 1 and n >= 6
    assert start + n == len(warm)
    # a monotone ramp never flattens
    assert steady_state_span([float(i) for i in range(10)]) is None
    # too short: no verdict
    assert steady_state_span([1.0] * 5) is None


def test_window_records_have_no_numpy_in_public_form(slo, tel):
    """The raw e2e delta ladder rides the internal record only; the
    JSON-facing forms must serialize cleanly."""
    slo.observe_pod({"e2e": 0.2}, pod="p", uid="u")
    tel.force_roll(None)
    assert "_e2e_counts" in tel.windows()[-1]
    json.dumps(tel.to_dict())          # raises if a ladder leaked


# ------------------------------------------- disarmed-cost + parity golden


def test_disarmed_hot_path_is_noop(monkeypatch):
    """Ring disarmed: a full scheduling cycle must never construct a
    TelemetryRing, tick, roll, or gather — the one-attribute-read
    contract, enforced with the poison-monkeypatch pattern of
    tests/test_slo.py / test_flightrecorder.py."""
    utelemetry.disarm_telemetry()

    def boom(*a, **kw):
        raise AssertionError("hot path touched the disarmed telemetry "
                             "plane")

    monkeypatch.setattr(utelemetry.TelemetryRing, "__init__", boom)
    monkeypatch.setattr(utelemetry.TelemetryRing, "maybe_tick", boom)
    monkeypatch.setattr(utelemetry.TelemetryRing, "force_roll", boom)

    store, sched = _world()
    try:
        outs = _drain(sched)
        assert sum(1 for o in outs if o.node) == 6
    finally:
        sched.close()


def test_golden_world_parity_armed_vs_disarmed():
    """Arming the telemetry ring changes ZERO placements: the same
    deterministic world drained armed (with ticks forced every cycle)
    and disarmed must bind every pod identically."""
    def run(arm):
        utelemetry.disarm_telemetry()
        if arm:
            # microscopic window: every schedule_pending call rolls
            utelemetry.arm_telemetry(window_s=1e-3)
        try:
            store, sched = _world(n_nodes=3, n_pods=12, batch=4)
            try:
                outs = _drain(sched)
                return sorted((o.pod.metadata.name, o.node) for o in outs)
            finally:
                sched.close()
        finally:
            utelemetry.disarm_telemetry()

    disarmed = run(False)
    armed = run(True)
    assert armed == disarmed
    assert sum(1 for _, node in armed if node) == 12


# ------------------------------------------------- chaos-storm attribution


def test_chaos_recoveries_land_in_firing_window(tel):
    """A seeded dispatch-error storm: the recovery events (and any
    demotions they carry) are attributed to the window that was OPEN
    when the recovery ladder fired — earlier and later windows stay
    clean (the object-identity tail scan on sched.recovery_log)."""
    store = ClusterStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
        pod_initial_backoff_seconds=0.01,
        pod_max_backoff_seconds=0.05), async_binding=False)
    for p in hollow.make_pods(4):
        store.add(p)
    try:
        tel.force_roll(sched)                       # clean baseline
        assert tel.windows()[-1].get("recoveries", 0) == 0

        chaos.arm(chaos.ChaosRegistry(seed=1).arm_point(
            "dispatch", "error", n=1))
        try:
            # requeued pods land in backoff: flush between pops so the
            # retry cycle runs (the test_chaos.py drain pattern)
            outs, idle = [], 0
            while idle < 4:
                sched.queue.flush_backoff_completed()
                got = sched.schedule_pending(timeout=0.0)
                if got:
                    outs.extend(got)
                    idle = 0
                else:
                    idle += 1
                    time.sleep(0.02)
        finally:
            chaos.disarm()
        assert sum(1 for o in outs if o.node) == 4
        assert sched.recovery_log
        w = tel.force_roll(sched)                   # the firing window
        assert w["recoveries"] == len(sched.recovery_log)
        kinds = [e["kind"] for e in w["recovery_events"]]
        assert "dispatch-error" in kinds
        # demotions are the summed demoted-lists of exactly this
        # window's events (a lax world demotes nothing; a synthetic
        # demotion below proves the counting seam)
        assert w["demotions"] == sum(
            len(e.get("demoted") or ()) for e in sched.recovery_log)

        sched.recovery_log.append(
            {"kind": "dispatch-error", "cycle": 99,
             "demoted": ["aot->trace"]})
        w2 = tel.force_roll(sched)
        assert w2["recoveries"] == 1 and w2["demotions"] == 1

        w3 = tel.force_roll(sched)                  # quiet again
        assert w3["recoveries"] == 0 and w3["demotions"] == 0
        assert tel.digest()["demotions"] == 1
    finally:
        sched.close()


# ------------------------------------------------------------------ HTTP


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}") as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read().decode())


def test_debug_loadz_disarmed_404():
    utelemetry.disarm_telemetry()
    store, sched = _world(n_pods=0)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        code, doc = _get(port, "/debug/loadz")
        assert code == 404 and doc["armed"] is False
        assert "KUBETPU_TELEMETRY" in doc["hint"]
    finally:
        srv.stop()
        sched.close()


def test_debug_loadz_http_roundtrip(slo, tel):
    store, sched = _world()
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        _drain(sched)
        tel.force_roll(sched)
        tel.force_roll(sched)
        code, doc = _get(port, "/debug/loadz")
        assert code == 200 and doc["armed"] is True
        assert doc["digest"]["windows"] == len(doc["windows"]) == 2
        w = doc["windows"][0]
        assert w["stages"]["e2e"]["count"] == 6
        assert "queue_depths" in w and "cycles" in w
        assert "_e2e_counts" not in w

        code, doc = _get(port, "/debug/loadz?n=1")
        assert code == 200 and len(doc["windows"]) == 1
        assert doc["windows"][0]["seq"] == 2

        code, doc = _get(port, "/debug/loadz?n=-1")
        assert code == 400
        code, doc = _get(port, "/debug/loadz?n=bogus")
        assert code == 400
    finally:
        srv.stop()
        sched.close()


def test_metrics_window_series(slo, tel):
    """/metrics carries the scheduler_load_* window series while armed
    and drops them (byte-identically absent) when disarmed."""
    m = SchedulerMetrics()
    store, sched = _world(metrics=m)
    try:
        _drain(sched)
        tel.force_roll(sched)
        body = m.expose_text()
        assert "scheduler_load_windows_total 1" in body
        assert "scheduler_load_window_pods 6" in body
        assert "scheduler_load_window_e2e_p99_seconds" in body
        utelemetry.disarm_telemetry()
        assert "scheduler_load_" not in m.expose_text()
    finally:
        sched.close()


# ------------------------------------------------- streams + open loop


def test_streams_are_seeded_and_sorted():
    a = hollow.poisson_stream(50.0, 2.0, seed=9, mean_dwell_s=1.0)
    b = hollow.poisson_stream(50.0, 2.0, seed=9, mean_dwell_s=1.0)
    assert [(e["t"], e["kind"], e["pod"].metadata.name) for e in a] == \
           [(e["t"], e["kind"], e["pod"].metadata.name) for e in b]
    ts = [e["t"] for e in a]
    assert ts == sorted(ts)
    adds = [e for e in a if e["kind"] == "add"]
    dels = [e for e in a if e["kind"] == "delete"]
    assert adds and len(dels) == len(adds)     # every add departs
    first_add = {e["pod"].metadata.name: e["t"] for e in adds}
    assert all(e["t"] > first_add[e["pod"].metadata.name] for e in dels)

    burst = hollow.burst_stream(5.0, 21.0, seed=2, burst_every_s=10.0,
                                burst_size=16)
    spikes = [e for e in burst if e["t"] in (10.0, 20.0)]
    assert len(spikes) == 32                   # two full bursts

    di = hollow.diurnal_stream(30.0, 4.0, seed=3, period_s=2.0)
    assert di and all(0.0 <= e["t"] < 4.0 for e in di)


def test_sustained_runner_open_loop_smoke(slo, tel):
    """Seconds-scale open-loop smoke: the runner fires a short seeded
    stream at wall deadlines against a live serving scheduler, every
    offered pod completes, and the ring's digest rides the result."""
    store = ClusterStore()
    for n in hollow.make_nodes(4):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=32,
        prewarm=False), async_binding=True)
    sched.run()
    try:
        events = hollow.poisson_stream(40.0, 0.75, seed=5)
        res = SustainedLoadRunner(store, sched, events, 0.75,
                                  settle_s=30.0).run()
        assert res["offered"] == len(events)
        assert res["completed"] == res["offered"]
        assert res["completed_frac"] == 1.0
        assert res["behind_max_s"] < 30.0
        assert res["load"]["windows"] >= 1
        assert res["load"]["pods"] >= res["offered"]
    finally:
        sched.close()


@pytest.mark.slow
def test_sustained_soak_reaches_steady_state(slo):
    """Minutes-scale soak (tier-1 excludes it via -m 'not slow'): a
    sustained Poisson stream long enough for the slope test to find a
    steady suffix, with zero demotions and a bounded ring."""
    utelemetry.disarm_telemetry()
    utelemetry.arm_telemetry(window_s=2.0, capacity=512)
    store = ClusterStore()
    for n in hollow.make_nodes(16, zones=4):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=64),
        async_binding=True)
    sched.run()
    try:
        # warmup drip pays the pow2 batch buckets first (see
        # bench.py sustained_load_case for the full rationale)
        warm = hollow.make_pods(31, prefix="soak-warm-", group_labels=8)
        for k in (1, 2, 4, 8, 16):
            group, warm = warm[:k], warm[k:]
            for p in group:
                store.add(p)
            deadline = time.time() + 120.0
            while time.time() < deadline:
                if all(p.spec.node_name for p in group):
                    break
                time.sleep(0.05)
        events = hollow.poisson_stream(8.0, 60.0, seed=13,
                                       group_labels=8)
        res = SustainedLoadRunner(store, sched, events, 60.0,
                                  settle_s=60.0).run()
        load = res["load"]
        assert load["demotions"] == 0
        assert res["completed_frac"] >= 0.95
        steady = load.get("steady")
        assert steady is not None and steady["windows"] >= 6
        assert steady["p99_s"] > 0
        ring = utelemetry.ring()
        assert len(ring.windows()) <= ring.capacity
    finally:
        sched.close()
        utelemetry.disarm_telemetry()
