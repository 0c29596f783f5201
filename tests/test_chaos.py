"""Chaos harness + self-healing runtime (kubetpu/utils/chaos.py, the
deadline-guarded dispatch, the anti-entropy verifier, watch/bind/extender
transport recovery, and the disarmed no-op poison test).

Every scenario is a NAMED, SEEDED injection asserting its recovery
invariant: the serving path stays alive, no pod is lost, no pod binds
twice, and the device residents match the host mirror bit-for-bit after
recovery."""
import time

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import chaos
from kubetpu.utils.metrics import SchedulerMetrics


@pytest.fixture(autouse=True)
def _disarm():
    """Chaos and the aot demotion latch are process-global; every test
    starts and ends disarmed."""
    from kubetpu.utils import aot
    chaos.disarm()
    aot.reset_demotion()
    yield
    chaos.disarm()
    aot.disarm()
    aot.reset_demotion()


class CountingStore(ClusterStore):
    """ClusterStore that counts bind calls per pod — the no-double-bind
    oracle."""

    def __init__(self):
        super().__init__()
        self.bind_calls = []

    def bind(self, pod, node_name):
        self.bind_calls.append(pod.metadata.name)
        super().bind(pod, node_name)


def _sched(store, metrics=None, **kw):
    kw.setdefault("profiles", [KubeSchedulerProfile()])
    kw.setdefault("mode", "gang")
    # fast retry ladder so recovered pods clear backoff inside the test
    kw.setdefault("pod_initial_backoff_seconds", 0.01)
    kw.setdefault("pod_max_backoff_seconds", 0.05)
    return Scheduler(store, config=KubeSchedulerConfiguration(**kw),
                     async_binding=False, metrics=metrics)


def _drain(sched, max_idle=4):
    """Drain including requeued pods: flushes the backoff queue between
    pops (tests run without the queue's periodic flush threads)."""
    outs = []
    idle = 0
    while idle < max_idle:
        sched.queue.flush_backoff_completed()
        got = sched.schedule_pending(timeout=0.0)
        if got:
            outs.extend(got)
            idle = 0
        else:
            idle += 1
            time.sleep(0.03)
    return outs


def _placed(outs):
    return {o.pod.metadata.name: o.node for o in outs if o.node}


# ------------------------------------------------------------ spec parsing


def test_spec_parsing_and_determinism():
    reg = chaos.parse_spec("seed=7,dispatch:error:n=1,delta:corrupt:p=0.5")
    assert reg.decide("dispatch") == ("error", chaos.DEFAULT_STALL_S)
    assert reg.decide("dispatch") is None          # n=1 exhausted
    assert reg.counts() == {"dispatch": 1}
    # p=0.5 draws are deterministic for a given seed
    seq_a = [reg.decide("delta") is not None for _ in range(16)]
    reg2 = chaos.parse_spec("seed=7,delta:corrupt:p=0.5")
    seq_b = [reg2.decide("delta") is not None for _ in range(16)]
    assert seq_a == seq_b and any(seq_a) and not all(seq_a)


def test_spec_rejects_typos():
    with pytest.raises(ValueError):
        chaos.parse_spec("dispatchh:error")
    with pytest.raises(ValueError):
        chaos.parse_spec("dispatch:corrupt")       # mode not supported
    with pytest.raises(ValueError):
        chaos.parse_spec("dispatch:error:bogus=1")


def test_maybe_arm_from_env(monkeypatch):
    monkeypatch.setenv(chaos.ENV, "seed=3,bind:error:n=2")
    reg = chaos.maybe_arm_from_env()
    assert reg is not None and chaos.active() is reg
    assert reg.decide("bind") is not None
    chaos.disarm()


# --------------------------------------------------- dispatch error / stall


def test_dispatch_error_requeues_and_places_exactly_once():
    """Injection point `dispatch`, mode error: the cycle is recovered —
    pods requeued (never lost), residents invalidated — and the retry
    places every pod exactly once (no double binds)."""
    store = CountingStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    m = SchedulerMetrics()
    sched = _sched(store, metrics=m, batch_size=4)
    try:
        for p in hollow.make_pods(4, prefix="d-"):
            store.add(p)
        chaos.arm(chaos.ChaosRegistry(seed=1).arm_point(
            "dispatch", "error", n=1))
        outs = _drain(sched)
        placed = _placed(outs)
        assert len(placed) == 4                     # no pod lost
        assert sorted(store.bind_calls) == sorted(placed)   # exactly once
        # the first attempt surfaced as recovered outcomes, not silence
        recovered = [o for o in outs
                     if o.err and "dispatch recovered" in o.err]
        assert len(recovered) == 4
        assert sched.recovery_log
        assert sched.recovery_log[0]["kind"] == "dispatch-error"
        assert m.recoveries.value("dispatch-error") == 1
        assert m.faults_injected.value("dispatch") == 1
    finally:
        sched.close()


def test_dispatch_stall_blows_deadline_and_recovers():
    """Injection point `dispatch`, mode stall + an armed deadline: the
    late cycle is DISCARDED pre-commit (kind dispatch-deadline) and its
    pods place on the retry — never lost, never double-bound."""
    store = CountingStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    m = SchedulerMetrics()
    sched = _sched(store, metrics=m, batch_size=2)
    try:
        # warm until a whole wave drains with ZERO compile/cache-load
        # activity: compile activity legitimately exempts a cycle from
        # the deadline, so the stall must be the only slow thing left.
        # Deleting each wave's pods resets the world so every wave (and
        # the stall wave after) replays the SAME program variants —
        # leaving the pods in place would grow the existing-pod bucket
        # and re-compile forever
        from kubetpu.utils.sanitize import install_compile_timer
        timer = install_compile_timer()
        for wave in range(6):
            snap = timer.snapshot()
            pods = hollow.make_pods(2, prefix=f"w{wave}-")
            for p in pods:
                store.add(p)
            assert len(_placed(_drain(sched))) == 2
            clean = timer.snapshot() == snap
            for p in pods:
                store.delete(p)
            if clean:
                break
        else:
            pytest.fail("serving path never stopped compiling")
        sched._dispatch_deadline = 0.2
        chaos.arm(chaos.ChaosRegistry(seed=2).arm_point(
            "dispatch", "stall", n=1, delay=0.5))
        for p in hollow.make_pods(2, prefix="s-"):
            store.add(p)
        outs = _drain(sched)
        placed = _placed(outs)
        assert all(f"s-{i}" in placed for i in range(2))
        # every bind landed exactly once across both waves
        assert sorted(store.bind_calls) == sorted(
            set(store.bind_calls))
        kinds = [e["kind"] for e in sched.recovery_log]
        assert "dispatch-deadline" in kinds
        assert m.recoveries.value("dispatch-deadline") == 1
    finally:
        sched.close()


def test_deadline_exempts_first_compile():
    """A first-compile of a new bucket is legitimate, bounded work: the
    deadline guard subtracts CompileTimer-measured compile/cache-load
    seconds, so a healthy backend is never demoted over an XLA compile
    (only genuine device stalls trip the deadline)."""
    store = CountingStore()
    # 17 nodes -> a node bucket no other test in this process compiled,
    # so the first cycle pays a real multi-second XLA compile
    for n in hollow.make_nodes(17):
        store.add(n)
    sched = _sched(store, batch_size=4, prewarm=False,
                   dispatch_deadline_seconds=0.3)
    try:
        for p in hollow.make_pods(4, prefix="c-"):
            store.add(p)
        outs = _drain(sched)
        assert len(_placed(outs)) == 4
        assert not any(e["kind"] == "dispatch-deadline"
                       for e in sched.recovery_log)
    finally:
        sched.close()


@pytest.mark.parametrize("armed", [True, False])
def test_dispatch_error_ladder_is_aot_to_trace_then_requeue(armed, tmp_path):
    """The recovery ladder's one demotion: a dispatch fault with an AOT
    runtime armed disarms it (AOT -> trace) with the reason recorded;
    with nothing armed there is nothing to demote.  Either way the pods
    requeue and every one binds exactly once."""
    from kubetpu.utils import aot
    store = CountingStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    sched = _sched(store, batch_size=4)
    try:
        if armed:
            # an empty store: every dispatch misses and traces, which is
            # all the ladder needs to find a runtime to disarm
            aot.arm(aot.serve_runtime(str(tmp_path)))
        chaos.arm(chaos.ChaosRegistry(seed=3).arm_point(
            "dispatch", "error", n=1))
        for p in hollow.make_pods(4, prefix="p-", group_labels=0):
            store.add(p)
        outs = _drain(sched)
        assert len(_placed(outs)) == 4
        assert sorted(store.bind_calls) == [f"p-{i}" for i in range(4)]
        first = sched.recovery_log[0]
        assert first["kind"] == "dispatch-error"
        assert first["demoted"] == (["aot->trace"] if armed else [])
        assert aot.active_runtime() is None
        assert (aot.demotion_reason() or "").startswith(
            "dispatch-error") == armed
    finally:
        sched.close()


def test_pipelined_dispatch_error_loses_no_pods():
    """The pipelined drain's guarded dispatch: an injected fault inside
    the double-buffered path still requeues and places everything, with
    no double binds."""
    store = CountingStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    sched = _sched(store, batch_size=4, chain_cycles=True,
                   pipeline_cycles=True)
    try:
        chaos.arm(chaos.ChaosRegistry(seed=4).arm_point(
            "dispatch", "error", n=1))
        for p in hollow.make_pods(8, prefix="pl-"):
            store.add(p)
        outs = _drain(sched)
        outs.extend(sched.flush_pipeline())
        placed = _placed(outs)
        assert len(placed) == 8
        assert sorted(store.bind_calls) == sorted(placed)
        assert any(e["kind"] == "dispatch-error"
                   for e in sched.recovery_log)
    finally:
        sched.close()


# ------------------------------------------------- delta + anti-entropy


def _delta_world(monkeypatch, metrics=None):
    """Gang scheduler with the chain OFF (every cycle takes the
    DeltaTensorizer path) and the verifier on a 1-cycle cadence."""
    monkeypatch.setenv("KUBETPU_VERIFY_INTERVAL", "1")
    store = ClusterStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    sched = _sched(store, metrics=metrics, batch_size=2,
                   chain_cycles=False)
    return store, sched


@pytest.mark.parametrize("mode", ["drop", "corrupt"])
def test_delta_fault_caught_by_verifier(monkeypatch, mode):
    """Injection point `delta` (drop a scatter / corrupt a resident): the
    anti-entropy verifier detects mirror/device divergence on its next
    tick and triggers the targeted full resync; fingerprints match
    afterwards and every pod still places."""
    m = SchedulerMetrics()
    store, sched = _delta_world(monkeypatch, metrics=m)
    try:
        # cycle 1: initial resync (builds the residents)
        for p in hollow.make_pods(2, prefix="a-"):
            store.add(p)
        assert len(_placed(_drain(sched))) == 2
        name = next(iter(sched.profiles))
        delta = sched._delta[name]
        assert delta.divergence_count == 0
        # cycle 2: the binds dirtied node rows -> a scatter runs and the
        # armed fault drops/corrupts it; the verifier (cadence 1) must
        # catch the divergence in the SAME refresh and resync
        chaos.arm(chaos.ChaosRegistry(seed=5).arm_point("delta", mode,
                                                        n=1))
        for p in hollow.make_pods(2, prefix="b-"):
            store.add(p)
        outs = _drain(sched)
        assert len(_placed(outs)) == 2
        delta = sched._delta[name]
        assert delta.divergence_count == 1
        assert delta.verify()            # consistent after recovery
        assert m.recoveries.value("verify-resync") >= 1
        assert any(e["kind"] == "verify-resync"
                   for e in sched.recovery_log)
        assert m.faults_injected.value("delta") == 1
    finally:
        sched.close()


def test_mirror_never_aliased_into_donated_residents():
    """Regression for a real corruption the verifier caught: to_device
    leaves that zero-copy-alias the host mirror (jnp.asarray of a
    64-byte-aligned numpy buffer on CPU) get clobbered when the delta
    scatter DONATES the cluster — XLA reuses the aliased buffer for
    unrelated outputs, silently corrupting the MIRROR.  Small mirrors
    only align by malloc luck (a flaky false divergence); production-
    sized ones are page-aligned, so aliasing is the common case at
    scale.  Force the alignment and assert the device leaf owns its
    buffer and the fingerprints stay bit-identical through a donated
    scatter."""
    import numpy as np

    from kubetpu.state.cache import SchedulerCache, Snapshot
    from kubetpu.state.delta import DeltaTensorizer

    cache = SchedulerCache()
    nodes = hollow.make_nodes(3)
    for n in nodes:
        cache.add_node(n)
    p0 = hollow.make_pod("res-0")
    p0.spec.node_name = nodes[0].name
    cache.add_pod(p0)

    def infos():
        snap = Snapshot()
        cache.update_snapshot(snap)
        return snap.node_info_list

    dt = DeltaTensorizer(verify_interval=1)
    _, st = dt.refresh(infos())
    assert st.resync and st.reason == "initial"
    # swap the mirror's pod_valid for a 64-byte-aligned twin — the
    # zero-copy precondition — and re-upload the residents from it
    a = dt.host.arrays
    old = a["pod_valid"]
    buf = np.zeros(old.nbytes + 64, np.uint8)   # keep alive: owns memory
    off = (-buf.ctypes.data) % 64
    aligned = buf[off:off + old.nbytes].view(bool)
    aligned[:] = old
    assert aligned.ctypes.data % 64 == 0
    a["pod_valid"] = aligned
    dt._upload()
    assert (dt.cluster.pod_valid.unsafe_buffer_pointer()
            != aligned.ctypes.data)             # device owns a COPY
    # a donated scatter cycle must leave the mirror bit-consistent
    p1 = hollow.make_pod("res-1")
    p1.spec.node_name = nodes[1].name
    cache.add_pod(p1)
    _, st = dt.refresh(infos(), donate=True)
    assert not st.resync and st.delta_rows > 0
    assert dt.verify()
    assert dt.divergence_count == 0
    assert buf is not None


def test_verifier_consistent_run_never_resyncs_for_divergence(monkeypatch):
    """With the verifier armed but no fault injected, checks run on
    cadence and never report divergence — the fingerprint really is
    bit-stable across delta cycles."""
    store, sched = _delta_world(monkeypatch)
    try:
        for wave in range(3):
            for p in hollow.make_pods(2, prefix=f"w{wave}-"):
                store.add(p)
            _drain(sched, max_idle=2)
        delta = next(iter(sched._delta.values()))
        assert delta.verify_count >= 2
        assert delta.divergence_count == 0
    finally:
        sched.close()


# ------------------------------------------------------------- aot load


def _aot_world(tmp_path, program, sig_key, artifact):
    # program names are UNIQUE per test: aot's kwarg-defaults cache is
    # keyed by program name process-wide, so reusing test_aot.py's "f"
    # here would poison its signature tests (and vice versa)
    from kubetpu.utils import aot
    store = aot.AotStore(str(tmp_path))
    store.write_index(aot.env_signature(),
                      [{"row": f"serving:{program}@b2", "family": "serving",
                        "program": program, "sig_key": sig_key,
                        "artifact": artifact, "pod_bucket": 2}])
    return store


def test_truncated_artifact_degrades_with_reason(tmp_path):
    """Satellite: a truncated .aotx blob must degrade preload to the
    per-bucket trace fallback with the reason recorded — never fail
    prewarm, never poison dispatch."""
    import jax
    import numpy as np

    from kubetpu.utils import aot

    @jax.jit
    def f(x):
        return x * 3

    x = np.ones((2,), np.float32)
    key = aot.call_signature("f_chaos_trunc", f, (x,), {})[0]
    store = _aot_world(tmp_path, "f_chaos_trunc", key, "t.aotx")
    store.save("t.aotx", {"m": 1}, b"payload" * 64, None, None, [0])
    blob = (tmp_path / "t.aotx").read_bytes()
    (tmp_path / "t.aotx").write_bytes(blob[:len(blob) // 2])  # torn write
    rt = aot.AotRuntime(store, mode="serve")
    assert rt.disabled_reason is None
    report = rt.preload()
    assert len(report) == 1 and not report[0]["ok"]
    assert report[0]["reason"]          # the recorded why
    # trace fallback still serves
    out = rt.dispatch("f_chaos_trunc", f, (x,), {})
    assert np.array_equal(np.asarray(out), x * 3)
    assert rt.stats()["loads"] == 0


def test_chaos_aot_load_fault_degrades(tmp_path):
    """Injection point `aot-load`: chaos truncates an INTACT blob at read
    time; the load path degrades identically to the on-disk corruption
    case."""
    import jax
    import numpy as np

    from kubetpu.utils import aot

    @jax.jit
    def f(x):
        return x - 2

    x = np.ones((2,), np.float32)
    key = aot.call_signature("f_chaos", f, (x,), {})[0]
    store = _aot_world(tmp_path, "f_chaos", key, "c.aotx")
    store.save("c.aotx", {"m": 1}, b"payload" * 64, None, None, [0])
    reg = chaos.arm(chaos.ChaosRegistry(seed=6).arm_point(
        "aot-load", "corrupt", n=1))
    rt = aot.AotRuntime(store, mode="serve")
    report = rt.preload()
    assert len(report) == 1 and not report[0]["ok"]
    assert reg.counts() == {"aot-load": 1}
    out = rt.dispatch("f_chaos", f, (x,), {})
    assert np.array_equal(np.asarray(out), x - 2)


def test_aot_demotion_latch_blocks_env_rearm(monkeypatch, tmp_path):
    """After the recovery ladder demotes AOT->trace, a later Scheduler
    construction in the same process must NOT silently re-arm the
    artifact set that just faulted; reset_demotion() clears the latch."""
    from kubetpu.utils import aot

    aot.disarm(reason="dispatch-deadline: test")
    monkeypatch.setenv(aot.DIR_ENV, str(tmp_path))
    monkeypatch.setattr(
        aot, "serve_runtime",
        lambda root: pytest.fail("demoted runtime re-armed from env"))
    assert aot.maybe_arm_from_env() is None
    assert aot.demotion_reason().startswith("dispatch-deadline")


# ------------------------------------------------------------ bind retry


def test_flaky_bind_retries_and_places_exactly_once():
    """Satellite: a transient bind failure retries on the pod backoff
    ladder and the placement lands exactly once — the client bind is
    reached exactly one time (the injected fault fired before it)."""
    store = CountingStore()
    store.add(hollow.make_node("n1"))
    m = SchedulerMetrics()
    sched = _sched(store, metrics=m, batch_size=1, bind_retries=2)
    try:
        chaos.arm(chaos.ChaosRegistry(seed=7).arm_point("bind", "error",
                                                        n=1))
        store.add(hollow.make_pod("flaky"))
        outs = _drain(sched)
        assert _placed(outs) == {"flaky": "n1"}
        assert store.bind_calls == ["flaky"]        # exactly once
        assert store.get_pod("default", "flaky").spec.node_name == "n1"
        assert m.recoveries.value("bind-retry") == 1
    finally:
        sched.close()


def test_lost_bind_response_recovers_without_double_bind():
    """Bind is NOT idempotent (BindingREST Conflicts on any re-bind), so
    the retry ladder must detect the applied-but-response-lost case via
    the API instead of re-POSTing into a Conflict and failing a pod that
    is actually bound."""
    class LostResponseStore(CountingStore):
        def __init__(self):
            super().__init__()
            self.lose = 1

        def bind(self, pod, node_name):
            super().bind(pod, node_name)       # server applied it...
            if self.lose:
                self.lose -= 1                 # ...but the response died
                raise OSError("connection reset by peer")

    store = LostResponseStore()
    store.add(hollow.make_node("n1"))
    m = SchedulerMetrics()
    sched = _sched(store, metrics=m, batch_size=1, bind_retries=2)
    try:
        store.add(hollow.make_pod("lost"))
        outs = _drain(sched)
        assert _placed(outs) == {"lost": "n1"}
        assert store.bind_calls == ["lost"]     # ONE POST, no Conflict
        assert store.get_pod("default", "lost").spec.node_name == "n1"
        assert m.recoveries.value("bind-retry") == 1
    finally:
        sched.close()


def test_bind_retries_exhausted_fails_pod_cleanly():
    """When every retry fails, the pod goes through the normal failure
    path (forgotten + requeued) — not bound, not lost, not crashed."""
    store = CountingStore()
    store.add(hollow.make_node("n1"))
    sched = _sched(store, batch_size=1, bind_retries=1)
    try:
        chaos.arm(chaos.ChaosRegistry(seed=8).arm_point("bind", "error"))
        store.add(hollow.make_pod("doomed"))
        out = sched.schedule_pending(timeout=0.0)
        assert len(out) == 1 and out[0].err
        assert store.bind_calls == []
        assert store.get_pod("default", "doomed").spec.node_name == ""
        # the pod is requeued, not lost
        assert len(sched.queue) == 1
    finally:
        sched.close()


# -------------------------------------------------------- watch / rest


def test_dead_server_reconnect_backs_off():
    """Satellite: a dead API server must cost capped-exponential sleeps,
    not a spinning core — the retry count over a 1 s window stays small
    and the computed delay grows."""
    from kubetpu.client.rest import RestClusterStore
    store = RestClusterStore("http://127.0.0.1:1")   # nothing listens
    try:
        time.sleep(1.0)
        # without backoff a refused connect loops thousands of times/s
        assert 1 <= store._watch_retries <= 12
        assert store._watch_backoff_s > 0.0
    finally:
        store.close()


def test_watch_disconnects_recover_and_mirror_converges():
    """Injection point `watch`: injected disconnects ride the same
    backoff ladder and the mirror still converges on the server state."""
    from kubetpu.api import types as api
    from kubetpu.client.rest import APIServer, RestClusterStore
    server_store = ClusterStore()
    srv = APIServer(server_store)
    port = srv.start()
    reg = chaos.arm(chaos.ChaosRegistry(seed=9).arm_point(
        "watch", "error", n=3))
    client = RestClusterStore(f"http://127.0.0.1:{port}")
    try:
        assert client.wait_for_cache_sync(5.0)
        server_store.add(hollow.make_node("w1"))
        deadline = time.time() + 10.0
        while time.time() < deadline:
            if client.get("Node", "w1") is not None:
                break
            time.sleep(0.05)
        assert client.get("Node", "w1") is not None
        assert reg.counts().get("watch", 0) >= 1
        assert isinstance(client.get("Node", "w1"), api.Node)
    finally:
        client.close()
        srv.stop()


# ------------------------------------------------------------- extender


def test_extender_transport_fault_fails_pod_and_requeues():
    """Injection point `extender`: a transient webhook error fails the
    pod cleanly (requeued, serving alive); an ignorable extender rides
    through the same fault."""
    store = ClusterStore()
    store.add(hollow.make_node("n1"))
    sched = _sched(store, batch_size=1, mode="sequential",
                   extenders=[{"urlPrefix": "http://127.0.0.1:1",
                               "filterVerb": "filter",
                               "ignorable": True}])
    try:
        chaos.arm(chaos.ChaosRegistry(seed=10).arm_point(
            "extender", "error", n=1))
        store.add(hollow.make_pod("ext"))
        outs = _drain(sched)
        # ignorable: the fault is tolerated and the pod places
        assert _placed(outs) == {"ext": "n1"}
    finally:
        sched.close()


# ------------------------------------------------------ serving survival


def test_serving_thread_survives_chaos_storm():
    """The integration invariant: with faults firing across points, the
    serving THREAD stays alive and keeps placing pods."""
    store = CountingStore()
    for n in hollow.make_nodes(3):
        store.add(n)
    sched = _sched(store, batch_size=4, prewarm=False)
    try:
        chaos.arm(chaos.ChaosRegistry(seed=11)
                  .arm_point("dispatch", "error", n=2)
                  .arm_point("bind", "error", n=1))
        t = sched.run()
        for p in hollow.make_pods(6, prefix="storm-"):
            store.add(p)
        deadline = time.time() + 30.0
        while time.time() < deadline:
            bound = sum(1 for p in store.list("Pod")
                        if p.spec.node_name)
            if bound == 6:
                break
            time.sleep(0.1)
        assert t.is_alive()
        bound = [p.metadata.name for p in store.list("Pod")
                 if p.spec.node_name]
        assert len(bound) == 6
        assert sorted(store.bind_calls) == sorted(bound)  # no doubles
    finally:
        sched.close()


# -------------------------------------------------------- disarmed no-op


def test_disarmed_hot_path_is_noop(monkeypatch):
    """Poison test (the flight recorder's pattern): chaos disarmed and
    the verifier off, a scheduling cycle must never construct a registry
    decision, never take the chaos lock, and never compute a
    fingerprint — zero locks, zero readbacks added to the hot path."""
    chaos.disarm()

    def boom(*a, **kw):
        raise AssertionError("disarmed hot path touched the chaos/verify "
                             "machinery")

    from kubetpu.state.delta import DeltaTensorizer
    monkeypatch.setattr(chaos.ChaosRegistry, "decide", boom)
    monkeypatch.setattr(DeltaTensorizer, "fingerprint_device", boom)
    monkeypatch.setattr(DeltaTensorizer, "fingerprint_host", boom)
    monkeypatch.setattr(DeltaTensorizer, "verify", boom)
    monkeypatch.delenv("KUBETPU_VERIFY_INTERVAL", raising=False)

    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    sched = _sched(store, batch_size=2, chain_cycles=False)
    try:
        for p in hollow.make_pods(4, prefix="quiet-"):
            store.add(p)
        outs = _drain(sched, max_idle=2)
        assert len(_placed(outs)) == 4
        assert not sched.recovery_log
    finally:
        sched.close()
