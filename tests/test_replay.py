"""Bit-exact replay rig (tools/kubereplay): the acceptance oracle — a
journaled 50+-cycle deterministic depth-4 pipelined drain (delta cycles,
resyncs, chained segments) replays to byte-identical placements; a
tampered record is attributed as the first divergent cycle with its
per-pod decision diff; corrupt records skip with a per-record reason and
break lineage only until the next resync anchor; counterfactual mode
reports NONZERO divergence for a changed score weight and ZERO for
pipelineDepth changes; sequential mode and seq windows replay too."""
import copy
import os
import shutil

import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import journal as ujournal
from kubetpu.utils.journal import (decode_record, encode_record,
                                   read_records, record_filename)
from tools.kubereplay import replay_journal
from tools.kubereplay.__main__ import main as kubereplay_main


def _hetero_world(n_nodes=12):
    """Mixed capacities + zones so the score plugins genuinely disagree
    (a symmetric world makes every positive reweighting argmax-neutral
    and the counterfactual check vacuous)."""
    store = ClusterStore()
    nodes = []
    for i in range(n_nodes):
        n = hollow.make_node(f"rp-node-{i}", zone=f"zone-{i % 3}",
                             region="region-0",
                             cpu_milli=8000 if i % 2 else 3000)
        nodes.append(n)
        store.add(n)
    return store, nodes


def _churned_drain(jdir, n_pods=416, batch=8, depth=4, churn_every=7):
    """Journal a deterministic drained world: depth-4 pipelined chained
    gang drain with external node churn every few cycles (chain breaks
    -> delta cycles; the first cycle and churn-driven rebuilds are the
    resync anchors)."""
    ujournal.disarm_journal()
    ujournal.arm_journal(jdir)
    store, nodes = _hetero_world()
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch, mode="gang",
        chain_cycles=True, pipeline_cycles=True, pipeline_depth=depth)
    sched = Scheduler(store, config=cfg, async_binding=False)
    try:
        for i, p in enumerate(hollow.make_pods(n_pods, prefix="rp-",
                                               group_labels=4,
                                               cpu_milli=150)):
            if i % 3 == 0:
                hollow.with_spread(p, api.LABEL_ZONE,
                                   when="ScheduleAnyway")
            store.add(p)
        outs = []
        i = 0
        while True:
            got = sched.schedule_pending(timeout=0.0)
            if not got:
                break
            outs.extend(got)
            i += 1
            if i % churn_every == 0:
                n = copy.deepcopy(nodes[i % len(nodes)])
                n.metadata.labels["flap"] = f"v{i}"
                store.update(n)
        outs.extend(sched.flush_pipeline())
        return outs, sched.cycle_count
    finally:
        sched.close()
        ujournal.disarm_journal()


@pytest.fixture(scope="module")
def churned(tmp_path_factory):
    """ONE expensive journaled drain shared by the suite (the replays
    against copies never mutate it), plus the three full-window replay
    reports the assertions share — the replays are the costly half, so
    they run once here, not once per test."""
    d = str(tmp_path_factory.mktemp("replay") / "journal")
    outs, cycles = _churned_drain(d)
    recs = [rec for _s, rec, _k in read_records(d)]
    return {"dir": d, "outcomes": outs, "cycles": cycles,
            "records": recs,
            "report": replay_journal(d),
            "cf_weight": replay_journal(d, counterfactual={
                "score_weights": {"PodTopologySpread": 0}}),
            "cf_depth": replay_journal(d, counterfactual={
                "pipeline_depth": 8})}


# --------------------------------------------------------- the oracle


def test_50_cycle_depth4_drain_replays_bit_identical(churned):
    """THE acceptance criterion: 50+ cycles, including delta cycles, at
    least one resync and a depth-4 pipelined segment, replay to
    byte-identical placements."""
    recs = churned["records"]
    assert len(recs) >= 50, f"only {len(recs)} cycles journaled"
    kinds = {r["input"] for r in recs}
    assert "delta" in kinds, "no delta cycle in the window"
    assert "resync" in kinds, "no resync anchor in the window"
    assert "chain" in kinds, "no chained segment in the window"
    # the depth-4 pipelined segment really overlapped (some cycle parked
    # in a nonzero ring slot)
    assert any(r["links"]["ring_slot"] > 0 for r in recs)
    assert all(r["links"]["pipeline_depth"] == 4 for r in recs)

    rep = churned["report"]
    assert rep["records"] == len(recs)
    assert rep["replayed"] == len(recs)
    assert rep["skipped"] == []
    assert rep["matched"] == len(recs)
    assert rep["bit_match"] is True
    assert rep["first_divergence"] is None


def test_divergence_attributed_to_first_divergent_cycle(churned, tmp_path):
    """A tampered record (one pod's chosen node flipped) must surface as
    the FIRST divergent cycle, with the per-pod decision diff naming the
    moved pod — and the replay stops there (the oracle already
    failed)."""
    d = str(tmp_path / "tampered")
    shutil.copytree(churned["dir"], d)
    # tamper a mid-window record: flip pod 0's chosen node row
    target = churned["records"][len(churned["records"]) // 2]
    seq = target["seq"]
    path = os.path.join(d, record_filename(seq))
    with open(path, "rb") as f:
        rec = decode_record(f.read())
    packed = np.array(rec["packed"])
    old = int(packed[0])
    packed[0] = (old + 1) % rec["n_nodes"]
    rec["packed"] = packed
    with open(path, "wb") as f:
        f.write(encode_record(rec))

    rep = replay_journal(d)
    assert rep["bit_match"] is False
    div = rep["first_divergence"]
    assert div is not None and div["seq"] == seq
    assert div["links"]["flight_seq"] == target["links"]["flight_seq"]
    moved = [p for p in div["pod_diff"]
             if p["pod"].endswith(rec["pods"][0][0])]
    assert moved, "the tampered pod is not in the decision diff"
    assert moved[0]["recorded_node"] != moved[0]["replayed_node"]
    # stopped at the first divergence by default
    assert rep["replayed"] <= rep["records"]
    assert len(rep["divergences"]) == 1


def test_corrupt_record_skips_with_reason_until_anchor(churned, tmp_path):
    """A corrupt record is skipped with a per-record reason (never an
    abort); downstream non-anchor records skip as broken-lineage until
    the next resync anchor, after which replay resumes bit-exact."""
    recs = churned["records"]
    # pick a delta record that is NOT immediately followed by a resync,
    # so broken-lineage genuinely propagates at least one record
    seq = None
    for i, r in enumerate(recs[:-1]):
        if r["input"] == "delta" and recs[i + 1]["input"] != "resync":
            seq = r["seq"]
            break
    assert seq is not None
    d = str(tmp_path / "corrupt")
    shutil.copytree(churned["dir"], d)
    path = os.path.join(d, record_filename(seq))
    blob = bytearray(open(path, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(path, "wb") as f:
        f.write(bytes(blob))

    rep = replay_journal(d)
    reasons = {s["seq"]: s["reason"] for s in rep["skipped"]}
    assert seq in reasons and "corrupt" in reasons[seq]
    assert any("broken-lineage" in r for r in reasons.values())
    # replay resumed at the next anchor and the resumed tail bit-matched
    assert rep["replayed"] == rep["matched"] > 0
    assert rep["bit_match"] is True
    assert rep["replayed"] + len(rep["skipped"]) == rep["records"]


# ------------------------------------- records from before the one backend


def _rewrite(d, seq, **fields):
    path = os.path.join(d, record_filename(seq))
    with open(path, "rb") as f:
        rec = decode_record(f.read())
    rec.update(fields)
    with open(path, "wb") as f:
        f.write(encode_record(rec))


def test_record_written_by_the_removed_backend_is_refused(churned,
                                                          tmp_path):
    """A record that says kernel_backend "pallas" was produced by a
    program that no longer exists: it is skipped with that reason, its
    lineage breaks until the next anchor, and nothing else diverges."""
    recs = churned["records"]
    anchors = [r["seq"] for r in recs if r["input"] == "resync"]
    seq = anchors[0] + 1
    hi = anchors[1] + 2
    d = str(tmp_path / "old-backend")
    shutil.copytree(churned["dir"], d)
    _rewrite(d, seq, kernel_backend="pallas")
    rep = replay_journal(d, window=(anchors[0], hi))
    reasons = {s["seq"]: s["reason"] for s in rep["skipped"]}
    assert "removed Pallas kernel backend" in reasons[seq]
    assert all("broken-lineage" in r for q, r in reasons.items()
               if q != seq)
    assert rep["replayed"] == rep["matched"] >= 3   # both anchors + tail
    assert rep["bit_match"] is True


def test_records_marked_lax_or_unmarked_replay(churned, tmp_path):
    """Journals written before the field went carry kernel_backend
    "lax"; new ones carry nothing.  Both replay bit-exact."""
    recs = churned["records"]
    assert all("kernel_backend" not in r for r in recs)
    lo = recs[0]["seq"]
    d = str(tmp_path / "marked-lax")
    shutil.copytree(churned["dir"], d)
    for r in recs[:8:2]:
        _rewrite(d, r["seq"], kernel_backend="lax")
    rep = replay_journal(d, window=(lo, lo + 7))
    assert rep["skipped"] == []
    assert rep["replayed"] == rep["matched"] == 8
    assert rep["bit_match"] is True


# -------------------------------------------------------- counterfactual


def test_counterfactual_score_weight_reports_divergence(churned):
    rep = churned["cf_weight"]
    cf = rep["counterfactual"]
    assert cf["divergent_cycles"] > 0, \
        "a zeroed spread weight must move placements in this world"
    assert cf["diverged_pods"] > 0
    util = cf["utilization"]
    assert util["recorded"]["placed"] == util["counterfactual"]["placed"]
    assert set(util["delta"]) == set(util["recorded"])
    # counterfactual mode measures, it does not gate
    assert rep["bit_match"] is None


def test_counterfactual_pipeline_depth_reports_zero_divergence(churned):
    """Executor depth never reaches a device program: a pipelineDepth
    counterfactual must report ZERO divergence on the same window that
    diverges under a score-weight change."""
    rep = churned["cf_depth"]
    cf = rep["counterfactual"]
    assert cf["cycles"] == len(churned["records"])
    assert cf["divergent_cycles"] == 0
    assert cf["diverged_pods"] == 0
    assert cf["utilization"]["delta"]["spread_std"] == 0.0


def test_counterfactual_unknown_plugin_is_per_record_skip(churned):
    rep = replay_journal(churned["dir"], counterfactual={
        "score_weights": {"NoSuchPlugin": 3}})
    assert rep["replayed"] == 0
    assert all("NoSuchPlugin" in s["reason"] for s in rep["skipped"][:1])


# ------------------------------------------------------------ windows


def test_window_replays_span_with_anchor_warmup(churned):
    """A mid-journal window replays bit-exact: lineage warms up from the
    nearest resync anchor before the window, and only the window's
    records are reported."""
    recs = churned["records"]
    anchors = [r["seq"] for r in recs if r["input"] == "resync"]
    assert len(anchors) >= 2
    lo = anchors[1] + 1          # starts PAST an anchor: warm-up needed
    hi = min(lo + 9, recs[-1]["seq"])
    rep = replay_journal(churned["dir"], window=(lo, hi))
    assert rep["considered"] == hi - lo + 1
    assert rep["replayed"] == rep["matched"] == rep["considered"]
    assert rep["bit_match"] is True


# ---------------------------------------------------- sequential mode


def test_sequential_mode_replays_bit_identical(tmp_path):
    """The sequential replay program journals and replays too (rotating
    start_index + RNG counter recorded per cycle)."""
    d = str(tmp_path / "seqj")
    ujournal.disarm_journal()
    ujournal.arm_journal(d)
    store, _nodes = _hetero_world(n_nodes=6)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8,
        mode="sequential", chain_cycles=False)
    sched = Scheduler(store, config=cfg, async_binding=False)
    try:
        for p in hollow.make_pods(48, prefix="sq-", group_labels=2,
                                  cpu_milli=150):
            store.add(p)
        outs = []
        while True:
            got = sched.schedule_pending(timeout=0.0)
            if not got:
                break
            outs.extend(got)
        assert sum(1 for o in outs if o.node) == 48
        cycles = sched.cycle_count
    finally:
        sched.close()
        ujournal.disarm_journal()
    recs = [r for _s, r, _k in read_records(d)]
    assert len(recs) == cycles
    assert {r["mode"] for r in recs} == {"sequential"}
    # the RNG fold counter is per-dispatch and strictly increasing
    counters = [r["rng_counter"] for r in recs]
    assert counters == sorted(counters) and len(set(counters)) == len(recs)
    rep = replay_journal(d)
    assert rep["bit_match"] is True
    assert rep["replayed"] == cycles


def test_multi_profile_journal_replays_per_profile_lineage(tmp_path):
    """Two profiles interleave independent resident lineages in one
    journal (the scheduler keeps one DeltaTensorizer per profile): the
    replay rig must track them separately — a global lineage would
    scatter profile A's deltas onto profile B's cluster and report a
    spurious divergence on a perfectly correct journal."""
    d = str(tmp_path / "multiprof")
    ujournal.disarm_journal()
    ujournal.arm_journal(d)
    store, _nodes = _hetero_world(n_nodes=8)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile(),
                  KubeSchedulerProfile(scheduler_name="second")],
        batch_size=8, mode="gang", chain_cycles=True)
    sched = Scheduler(store, config=cfg, async_binding=False)
    try:
        for i, p in enumerate(hollow.make_pods(64, prefix="mp-",
                                               group_labels=2,
                                               cpu_milli=150)):
            if i % 2:
                p.spec.scheduler_name = "second"
            store.add(p)
        outs = []
        while True:
            got = sched.schedule_pending(timeout=0.0)
            if not got:
                break
            outs.extend(got)
        assert sum(1 for o in outs if o.node) == 64
    finally:
        sched.close()
        ujournal.disarm_journal()
    recs = [r for _s, r, _k in read_records(d)]
    profiles = [r["profile"] for r in recs]
    assert len(set(profiles)) == 2
    # genuinely interleaved, not two contiguous runs
    assert any(a != b for a, b in zip(profiles, profiles[1:]))
    rep = replay_journal(d)
    assert rep["skipped"] == []
    assert rep["bit_match"] is True
    assert rep["replayed"] == len(recs)


# ------------------------------------------------------------------ CLI


def test_cli_bit_match_and_counterfactual(churned, capsys):
    """CLI round trips over a short window (the full-window oracle and
    counterfactual already ran in the shared fixture — the CLI test only
    exercises argument plumbing and rendering)."""
    recs = churned["records"]
    win = f"{recs[0]['seq']}:{recs[0]['seq'] + 7}"
    assert kubereplay_main([churned["dir"], "--window", win]) == 0
    out = capsys.readouterr().out
    assert "bit-match oracle HELD" in out
    assert kubereplay_main([churned["dir"], "--window", win,
                            "--counterfactual",
                            "scoreWeight:PodTopologySpread=0",
                            "--json"]) == 0
    import json
    doc = json.loads(capsys.readouterr().out)
    assert doc["counterfactual"]["cycles"] == 8
    assert "divergent_cycles" in doc["counterfactual"]


def test_cli_divergence_exit_code(churned, tmp_path, capsys):
    d = str(tmp_path / "cli-tamper")
    shutil.copytree(churned["dir"], d)
    target = churned["records"][3]
    path = os.path.join(d, record_filename(target["seq"]))
    rec = decode_record(open(path, "rb").read())
    packed = np.array(rec["packed"])
    packed[0] = (int(packed[0]) + 1) % rec["n_nodes"]
    rec["packed"] = packed
    with open(path, "wb") as f:
        f.write(encode_record(rec))
    assert kubereplay_main([d]) == 2
    assert "FIRST DIVERGENCE" in capsys.readouterr().out


def test_cli_missing_journal(tmp_path, capsys):
    assert kubereplay_main([str(tmp_path / "nope")]) == 1
