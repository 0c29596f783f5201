"""Depth-k pipelined executor (kubetpu/pipeline.py): depth-parity
placement goldens, the gather-window gating on free ring slots, per-slot
exemption accounting, ring-slot flight-recorder tags, and config/env
depth plumbing."""
import os
from types import SimpleNamespace

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.pipeline import (GATHER_WINDOW_S, InflightRing,
                              PipelinedExecutor, depth_from_env)
from kubetpu.scheduler import Scheduler


def _world(n_nodes=16, n_pods=64, group_labels=4):
    store = ClusterStore()
    for n in hollow.make_nodes(n_nodes, zones=4):
        store.add(n)
    return store, hollow.make_pods(n_pods, group_labels=group_labels)


def _sched(store, depth, batch_size=8, **kw):
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch_size,
        mode="gang", chain_cycles=True, pipeline_cycles=True,
        pipeline_depth=depth, **kw)
    return Scheduler(store, config=cfg, async_binding=False)


def _drain(sched, max_cycles=80):
    out = []
    for _ in range(max_cycles):
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        out.extend(got)
    out.extend(sched.flush_pipeline())
    return out


# ------------------------------------------------------------ depth parity


def test_depth_parity_placements_bit_identical():
    """The executor's core contract: the SAME world drained at depth 1
    (fully synchronous), 2 (the historical double-buffered chain) and 4
    produces BIT-IDENTICAL placements — every cycle dispatches against
    the previous cycle's speculative chain or the committed cache, never
    a state that can diverge."""
    placements = {}
    for depth in (1, 2, 4):
        store, pods = _world()
        sched = _sched(store, depth)
        for p in pods:
            store.add(p)
        out = _drain(sched)
        assert len(out) == 64, f"depth={depth}: {len(out)} outcomes"
        assert all(o.node for o in out), [
            (o.pod.metadata.name, o.err) for o in out if not o.node]
        assert len({o.pod.uid for o in out}) == 64, "a pod committed twice"
        hw = sched._pipeline.ring.high_water
        assert hw <= depth - 1, f"ring overfilled: {hw} at depth {depth}"
        placements[depth] = {o.pod.metadata.name: o.node for o in out}
        sched.close()
    assert placements[1] == placements[2] == placements[4]


def test_depth4_ring_actually_fills():
    """Depth > 2 must genuinely hold multiple dispatched-but-uncommitted
    cycles in flight (the high-water mark proves the overlap exists and
    isn't silently serialized)."""
    store, pods = _world(n_pods=64)
    sched = _sched(store, 4)
    for p in pods:
        store.add(p)
    out = _drain(sched)
    assert len(out) == 64
    assert sched._pipeline.ring.high_water >= 2
    sched.close()


def test_depth1_is_synchronous_no_outcome_lag():
    """Depth 1: every cycle commits before the next pop — one call with
    one batch queued returns that batch's outcomes (no parking, no lag),
    and nothing is ever left in flight."""
    store, pods = _world(n_pods=8)
    sched = _sched(store, 1, batch_size=8)
    for p in pods:
        store.add(p)
    first = sched.schedule_pending(timeout=0.0)
    assert len(first) == 8
    assert all(o.node for o in first)
    assert len(sched._pipeline.ring) == 0
    assert sched._pipeline.ring.high_water == 0
    assert sched.flush_pipeline() == []
    sched.close()


# ----------------------------------------------------- gather-window gating


def test_pop_timeout_gates_gather_window_on_free_slots():
    """The satellite fix: the 20 ms burst-gather window is gated on FREE
    pipeline slots, not on "any slot occupied" — a partially filled ring
    still coalesces arriving bursts; only a FULL ring pops non-blocking
    (the oldest commit must not wait), and an empty ring blocks the
    caller's full timeout."""
    ex = PipelinedExecutor(None, depth=4)   # pop_timeout needs no sched

    def slot():
        return SimpleNamespace(parked_t=0.0, host_exempt_s=0.0)

    # empty ring: the caller's timeout passes through untouched
    assert ex.pop_timeout(0.2) == 0.2
    assert ex.pop_timeout(None) is None
    assert ex.pop_timeout(0.0) == 0.0
    # partially filled: gather window allowed, bounded to 20 ms
    ex.ring.append(slot(), None)
    assert ex.pop_timeout(0.2) == GATHER_WINDOW_S
    assert ex.pop_timeout(0.005) == 0.005
    assert ex.pop_timeout(None) == GATHER_WINDOW_S
    assert ex.pop_timeout(0.0) == 0.0      # explicit non-blocking stays
    ex.ring.append(slot(), None)
    assert ex.pop_timeout(0.2) == GATHER_WINDOW_S
    # full ring (capacity 3): non-blocking, the oldest commit is due
    ex.ring.append(slot(), None)
    assert ex.pop_timeout(0.2) == 0.0
    assert ex.pop_timeout(None) == 0.0
    # depth 1 (capacity 0): always the caller's timeout — the
    # synchronous drain must not busy-spin the serving loop
    ex1 = PipelinedExecutor(None, depth=1)
    assert ex1.pop_timeout(0.2) == 0.2


def test_drain_passes_gated_timeouts_to_pop_batch(monkeypatch):
    """Integration: the queue actually sees the gated timeouts — 0 only
    when the ring is full, the caller's timeout when it is empty, the
    gather window in between."""
    store, pods = _world(n_pods=48)
    sched = _sched(store, 4, batch_size=4)
    seen = []
    orig = sched.queue.pop_batch

    def spy(max_batch, timeout=None):
        seen.append((len(sched._pipeline.ring), timeout))
        return orig(max_batch, timeout=timeout)

    monkeypatch.setattr(sched.queue, "pop_batch", spy)
    for p in pods:
        store.add(p)
    out = _drain(sched)
    assert len(out) == 48
    cap = sched._pipeline.ring.capacity
    for ring_len, timeout in seen:
        if ring_len == 0:
            assert timeout == 0.0          # the test drain's timeout
        elif ring_len >= cap:
            assert timeout == 0.0
        else:
            assert 0.0 <= timeout <= GATHER_WINDOW_S
    sched.close()


# --------------------------------------------------- exemption accounting


def test_ring_park_unpark_exempt_accounting():
    """Per-slot deadline-exemption bookkeeping: parked think time folds
    into host_exempt_s on unpark, exempt() charges every un-parked slot,
    and parked slots are skipped (their whole window already accrues)."""
    ring = InflightRing(capacity=3)
    a = SimpleNamespace(parked_t=0.0, host_exempt_s=0.0)
    b = SimpleNamespace(parked_t=0.0, host_exempt_s=0.0)
    ring.append(a, None)
    ring.append(b, None)
    ring.park(100.0)
    assert a.parked_t == 100.0 and b.parked_t == 100.0
    # exempt() while parked is a no-op (no double counting)
    ring.exempt(5.0)
    assert a.host_exempt_s == 0.0 and b.host_exempt_s == 0.0
    ring.unpark(101.5)
    assert a.host_exempt_s == pytest.approx(1.5)
    assert b.host_exempt_s == pytest.approx(1.5)
    assert a.parked_t == 0.0
    ring.exempt(0.25)
    assert a.host_exempt_s == pytest.approx(1.75)
    assert b.host_exempt_s == pytest.approx(1.75)
    # pop_oldest is FIFO and detach_all empties
    assert ring.pop_oldest()[0] is a
    assert [p for p, _ in ring.detach_all()] == [b]
    assert len(ring) == 0


def test_inflight_cycles_accrue_exemptions_at_depth():
    """A real depth-4 drain: cycles that sat in the ring while other
    cycles committed carry a positive host_exempt_s by their own commit
    time (the per-slot generalization of PR 9's single-slot rule)."""
    store, pods = _world(n_pods=48)
    sched = _sched(store, 4, batch_size=4)
    exempts = []
    orig = sched._commit_group

    def spy(prep, packed):
        exempts.append(prep.host_exempt_s)
        return orig(prep, packed)

    sched._commit_group = spy
    for p in pods:
        store.add(p)
    out = _drain(sched)
    assert len(out) == 48
    assert any(e > 0 for e in exempts), \
        "no in-flight cycle accrued commit/park exemptions at depth 4"
    sched.close()


# ------------------------------------------------------- flight recorder


def test_ring_slot_tag_on_cycle_records():
    """Every pipelined cycle record carries ring_slot + pipeline_depth
    meta, and traceview's pipeline digest renders the occupancy."""
    from kubetpu.utils import trace as utrace
    import tools.traceview as tv

    fr = utrace.arm_flight_recorder(capacity=32)
    fr.clear()
    try:
        store, pods = _world(n_pods=48)
        sched = _sched(store, 4, batch_size=4)
        for p in pods:
            store.add(p)
        out = _drain(sched)
        assert len(out) == 48
        doc = fr.to_pipeline_doc(workload="test")
        metas = [c.get("meta", {}) for c in doc.get("cycle_meta", [])]
        slots = [m["ring_slot"] for m in metas if "ring_slot" in m]
        assert slots, "no cycle record carried a ring_slot tag"
        assert any(s > 0 for s in slots), \
            "every cycle parked at slot 0 — the overlap never deepened"
        assert all(m.get("pipeline_depth") == 4
                   for m in metas if "ring_slot" in m)
        digest = tv.pipeline_summary(doc)
        assert digest.startswith("pipeline: depth 4")
        assert "slot1:" in digest or "slot2:" in digest
        sched.close()
    finally:
        utrace.disarm_flight_recorder()


# -------------------------------------------------------- config plumbing


def test_config_decode_and_validate_pipeline_depth():
    from kubetpu.apis.load import ConfigError, load_config

    cfg = load_config({
        "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
        "kind": "KubeSchedulerConfiguration",
        "mode": "gang", "pipelineCycles": True, "pipelineDepth": 4,
    })
    assert cfg.pipeline_cycles is True
    assert cfg.pipeline_depth == 4
    with pytest.raises(ConfigError, match="pipelineDepth"):
        load_config({
            "apiVersion": "kubescheduler.config.k8s.io/v1beta1",
            "kind": "KubeSchedulerConfiguration",
            "pipelineDepth": 0,
        })


def test_env_depth_override(monkeypatch):
    """KUBETPU_PIPELINE_DEPTH re-depths a live fleet over the config."""
    monkeypatch.setenv("KUBETPU_PIPELINE_DEPTH", "5")
    assert depth_from_env(2) == 5
    store, _ = _world(n_pods=0)
    sched = _sched(store, 2)
    assert sched._pipeline.depth == 5
    assert sched._pipeline.ring.capacity == 4
    sched.close()
    monkeypatch.setenv("KUBETPU_PIPELINE_DEPTH", "0")
    assert depth_from_env(2) == 1          # clamped, never < 1
    monkeypatch.setenv("KUBETPU_PIPELINE_DEPTH", "junk")
    assert depth_from_env(3) == 3          # unparseable -> config value
    monkeypatch.delenv("KUBETPU_PIPELINE_DEPTH")
    assert depth_from_env(2) == 2


def test_flush_pipeline_returns_every_parked_outcome():
    """flush_pipeline at depth 4 commits the whole ring oldest-first;
    nothing is lost between a partial drain and the flush."""
    store, pods = _world(n_pods=32)
    sched = _sched(store, 4, batch_size=4)
    for p in pods:
        store.add(p)
    out = []
    # stop mid-drain with cycles still parked in the ring
    for _ in range(4):
        out.extend(sched.schedule_pending(timeout=0.0))
    out.extend(sched.flush_pipeline())
    assert len(sched._pipeline.ring) == 0
    # the rest of the backlog drains normally
    out.extend(_drain(sched))
    assert len(out) == 32
    assert all(o.node for o in out)
    assert len({o.pod.uid for o in out}) == 32
    sched.close()
