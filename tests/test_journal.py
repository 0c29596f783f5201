"""Durable cycle journal (kubetpu/utils/journal.py): record framing +
schema, every committed cycle journaled, size-cap eviction counted
(never silent), the chaos ``journal`` point's degrade-to-drop write
contract, corrupt-record skip reasons at read time, the disarmed
zero-lock hot-path poison test, armed-vs-disarmed placement parity,
scheduler_journal_* metric sync, /debug/journal and the traceview
"journal:" digest."""
import copy
import json
import os
import urllib.request

import numpy as np
import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.server import SchedulerServer
from kubetpu.utils import chaos
from kubetpu.utils import journal as ujournal
from kubetpu.utils import trace as utrace
from kubetpu.utils.journal import (CycleJournal, JournalCorrupt,
                                   decode_record, encode_record,
                                   read_records, record_filename)
from kubetpu.utils.metrics import SchedulerMetrics


@pytest.fixture
def jdir(tmp_path):
    """Armed journal in a tempdir; always disarmed on exit (module
    global, like the flight recorder's fixture)."""
    ujournal.disarm_journal()
    d = str(tmp_path / "journal")
    jr = ujournal.arm_journal(d)
    try:
        yield d, jr
    finally:
        ujournal.disarm_journal()


def _world(n_nodes=4, zones=2):
    store = ClusterStore()
    for n in hollow.make_nodes(n_nodes, zones=zones):
        store.add(n)
    return store


def _sched(store, batch=8, depth=2, **kw):
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch, mode="gang",
        chain_cycles=True, pipeline_cycles=depth > 1,
        pipeline_depth=depth, **kw)
    return Scheduler(store, config=cfg, async_binding=False)


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        outs.extend(got)
    outs.extend(sched.flush_pipeline())
    return outs


# ------------------------------------------------------------- framing


def test_record_framing_roundtrip_and_corruption():
    rec = {"seq": 7, "cycle": 3, "packed": np.arange(5, dtype=np.int32)}
    blob = encode_record(rec)
    back = decode_record(blob)
    assert back["seq"] == 7
    assert np.array_equal(back["packed"], rec["packed"])
    with pytest.raises(JournalCorrupt, match="truncated"):
        decode_record(blob[: len(blob) // 2])
    with pytest.raises(JournalCorrupt, match="magic"):
        decode_record(b"XXXXX" + blob[5:])
    flipped = bytearray(blob)
    flipped[-1] ^= 0xFF
    with pytest.raises(JournalCorrupt, match="crc"):
        decode_record(bytes(flipped))
    with pytest.raises(JournalCorrupt):
        decode_record(b"")


# ----------------------------------------------------- recording cycles


def test_every_committed_cycle_journaled(jdir):
    d, jr = jdir
    store = _world()
    sched = _sched(store, batch=8, depth=2)
    try:
        for p in hollow.make_pods(32, group_labels=2):
            store.add(p)
        outs = _drain(sched)
        assert sum(1 for o in outs if o.node) == 32
        entries = list(read_records(d))
        assert entries, "no records journaled"
        assert all(skip is None for _s, _r, skip in entries)
        assert len(entries) == sched.cycle_count
        seqs = [s for s, _r, _k in entries]
        assert seqs == sorted(seqs)
        first = entries[0][1]
        # the first record must be the replay anchor
        assert first["input"] == "resync"
        assert first["node_names"] is not None
        for _s, rec, _k in entries:
            assert rec["input"] in ujournal.INPUT_KINDS
            assert rec["mode"] == "gang"
            assert rec["packed"].dtype == np.int32
            assert len(rec["pods"]) == rec["verdicts"]["scheduled"] + \
                rec["verdicts"]["failed"]
            assert rec["links"]["decision_cycle"] == rec["cycle"]
            assert rec["links"]["pipeline_depth"] == 2
            assert rec["config_digest"] == first["config_digest"]
        st = jr.status()
        assert st["records"] == len(entries)
        assert st["dropped_total"] == 0
        assert st["bytes"] > 0
    finally:
        sched.close()


def test_armed_vs_disarmed_placement_parity(tmp_path):
    """Arming the journal changes ZERO placements — it only observes."""
    def run(arm):
        ujournal.disarm_journal()
        if arm:
            ujournal.arm_journal(str(tmp_path / "parity"))
        try:
            store = _world(n_nodes=3)
            sched = _sched(store, batch=4, depth=4)
            try:
                for p in hollow.make_pods(24, group_labels=3):
                    store.add(p)
                outs = _drain(sched)
                return sorted((o.pod.metadata.name, o.node) for o in outs)
            finally:
                sched.close()
        finally:
            ujournal.disarm_journal()

    assert run(True) == run(False)


def test_disarmed_hot_path_is_noop(monkeypatch):
    """Journal disarmed: a full pipelined drain must never construct a
    CycleJournal, reserve a seq, build a record, or touch the delta
    capture seam — the zero-new-locks contract, enforced with the same
    poison-monkeypatch pattern as trace/chaos."""
    ujournal.disarm_journal()

    def boom(*a, **kw):
        raise AssertionError("hot path touched the disarmed journal")

    monkeypatch.setattr(ujournal.CycleJournal, "__init__", boom)
    monkeypatch.setattr(ujournal.CycleJournal, "append", boom)
    monkeypatch.setattr(ujournal.CycleJournal, "next_seq", boom)
    monkeypatch.setattr(Scheduler, "_journal_append", boom)
    # pickling the mirror is the capture's allocation: disarmed, the
    # seam (_capture_resync / _apply — gates, one attribute read each)
    # must never reach it
    import kubetpu.state.delta as kdelta
    monkeypatch.setattr(kdelta.pickle, "dumps", boom)

    store = _world()
    sched = _sched(store, batch=8, depth=4)
    try:
        for p in hollow.make_pods(24, group_labels=2):
            store.add(p)
        outs = _drain(sched)
        assert sum(1 for o in outs if o.node) == 24
        # and the capture seam allocated nothing
        for delta in sched._delta.values():
            assert delta.capture is None
    finally:
        sched.close()


# ------------------------------------------------------------ size cap


def test_size_cap_eviction_counted_never_silent(tmp_path):
    ujournal.disarm_journal()
    jr = ujournal.arm_journal(str(tmp_path / "cap"), max_bytes=40_000)
    try:
        store = _world()
        sched = _sched(store, batch=4, depth=2)
        try:
            for p in hollow.make_pods(32, group_labels=2):
                store.add(p)
            _drain(sched)
            records, dropped = jr.counters()
            assert records == sched.cycle_count
            assert dropped > 0, "size cap never evicted"
            assert jr.disk_bytes() <= 40_000
            # evicted files really are gone; survivors are the newest
            entries = list(read_records(jr.dir))
            assert len(entries) == records - dropped
            assert entries[0][0] > 1
            st = jr.status()
            assert st["dropped_total"] == dropped
        finally:
            sched.close()
    finally:
        ujournal.disarm_journal()


def test_malformed_max_bytes_env_falls_back(tmp_path, monkeypatch):
    """KUBETPU_JOURNAL_MAX_BYTES junk must not crash arming (and so
    Scheduler construction) — it falls back to the default with a
    warning."""
    monkeypatch.setenv(ujournal.MAX_BYTES_ENV, "256MiB")
    j = CycleJournal(str(tmp_path / "junk-env"))
    assert j.max_bytes == ujournal.DEFAULT_MAX_BYTES


def test_restarted_journal_resumes_seq(tmp_path):
    d = str(tmp_path / "resume")
    j1 = CycleJournal(d)
    s1 = j1.next_seq()
    assert j1.append({"seq": s1, "cycle": 1, "links": {}})
    j2 = CycleJournal(d)
    assert j2.next_seq() == s1 + 1
    assert j2.counters() == (0, 0)   # fresh process counters
    assert j2.seqs() == [s1]


# ------------------------------------------------------------- metrics


def test_journal_metrics_synced(jdir):
    d, jr = jdir
    metrics = SchedulerMetrics()
    store = _world()
    sched = _sched(store, batch=8, depth=2)
    sched.metrics = metrics
    try:
        for p in hollow.make_pods(16, group_labels=2):
            store.add(p)
        _drain(sched)
        text = metrics.expose_text()
        assert "scheduler_journal_records_total" in text
        assert "scheduler_journal_bytes" in text
        assert "scheduler_journal_dropped_total" in text
        records, dropped = jr.counters()
        assert records == sched.cycle_count
        assert (f"scheduler_journal_records_total {float(records)}"
                in text or f"scheduler_journal_records_total {records}"
                in text)
    finally:
        sched.close()


# ------------------------------------------------------ chaos "journal"


def test_chaos_write_error_degrades_to_drop(jdir):
    """An injected journal write fault drops the record WITH the metric
    bumped — the cycle itself must commit normally."""
    d, jr = jdir
    chaos.disarm()
    chaos.arm(chaos.ChaosRegistry(seed=3).arm_point("journal", "error",
                                                    n=2))
    try:
        store = _world()
        sched = _sched(store, batch=8, depth=2)
        try:
            for p in hollow.make_pods(24, group_labels=2):
                store.add(p)
            outs = _drain(sched)
            assert sum(1 for o in outs if o.node) == 24
            records, dropped = jr.counters()
            assert dropped == 2
            assert records == sched.cycle_count - 2
            assert len(list(read_records(d))) == records
        finally:
            sched.close()
    finally:
        chaos.disarm()


def test_chaos_truncate_and_corrupt_skipped_at_read(jdir):
    """journal:truncate / journal:corrupt land a damaged frame on disk;
    the reader yields a per-record skip reason instead of aborting."""
    d, jr = jdir
    chaos.disarm()
    chaos.arm(chaos.ChaosRegistry(seed=1)
              .arm_point("journal", "truncate", n=1))
    try:
        store = _world()
        sched = _sched(store, batch=8, depth=1)
        try:
            for p in hollow.make_pods(24, group_labels=2):
                store.add(p)
            _drain(sched)
        finally:
            sched.close()
    finally:
        chaos.disarm()
    entries = list(read_records(d))
    skips = [(s, why) for s, _r, why in entries if why is not None]
    assert len(skips) == 1
    assert "truncated" in skips[0][1]
    # the rest decode fine
    assert sum(1 for _s, r, _w in entries if r is not None) \
        == len(entries) - 1


# ----------------------------------------------------------- endpoints


def test_debug_journal_endpoint_and_traceview(jdir):
    """ONE armed drain (journal + flight recorder) checked on both
    satellite surfaces: the /debug/journal status endpoint with linkage
    hit-rates, and the traceview "journal:" digest from the pipeline
    doc."""
    from tools.traceview import journal_summary
    d, jr = jdir
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=8)
    store = _world()
    sched = _sched(store, batch=8, depth=2)
    server = SchedulerServer(sched, port=0)
    port = server.start()
    try:
        for p in hollow.make_pods(16, group_labels=2):
            store.add(p)
        _drain(sched)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/journal") as r:
            doc = json.load(r)
        assert doc["armed"] is True
        assert doc["records"] == sched.cycle_count
        assert doc["bytes"] > 0
        assert doc["flight_link_rate"] == 1.0
        assert doc["flight_live_rate"] > 0.0
        assert "decision_live_rate" in doc
        assert "kubereplay" in doc["replay_hint"]
        assert doc["records"] == jr.counters()[0]
        # the pipeline doc carries the journal block; traceview digests
        pdoc = fr.to_pipeline_doc(workload="journal-digest-test")
        assert pdoc["journal"]["armed"] is True
        assert pdoc["journal"]["records"] == sched.cycle_count
        line = journal_summary(pdoc)
        assert line.startswith("journal: ")
        assert f"{sched.cycle_count} records" in line
        assert "flight-link 100%" in line
        assert journal_summary({"journal": {"armed": False}}) == ""
        assert journal_summary({}) == ""
    finally:
        server.stop()
        sched.close()
        utrace.disarm_flight_recorder()
