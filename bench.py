"""Headline benchmark: END-TO-END scheduling throughput.

Drives the full serving path — store -> queue -> snapshot -> tensorize ->
device program -> Reserve/assume -> bind — through Scheduler.schedule_pending
with the full default plugin matrix (reference:
pkg/scheduler/algorithmprovider/registry.go:77-160), the same loop shape as
the reference's scheduler_perf density benchmark whose hard floor is
30 pods/s (reference: test/integration/scheduler_perf/scheduler_test.go:
40-41,81-87).  The headline mode is the conflict-free gang auction
(kubetpu/models/gang.py); the sequential-replay scan (exact serial
semantics, scheduler.go:509) is reported in the detail line.

Device wait is measured at the serving path's one host sync: the
scheduler's single per-cycle packed readback (Scheduler.device_wait_s).
Dispatch is asynchronous, so wall-clock around dispatch alone measures the
enqueue.

Extra cases in the detail line:
- "chain_drain": the 4096-pod workload drained in 1024-pod cycles with
  cycle chaining ON vs OFF — the multi-cycle serving shape (VERDICT r3 #3).
- BENCH_FULL=1 adds the BASELINE.md north-star shapes (>=10k nodes) and
  writes NORTHSTAR.json: 10k x 5k InterPodAffinity-heavy e2e and a
  100k x 10k streaming rescore (score-only, autoscaler-simulate) with HBM
  accounting.

Every unscheduled pod is attributed to the filter(s) that blocked it
(programs.explain_filters) — no unexplained failures.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"repeat_raw_s", "spread"} — per-repeat raw numbers and the min/median
warm spread ride next to the best-of headline so regressions are
distinguishable from run-to-run variance.  BENCH_OUT=<path> additionally
writes {"headline", "detail"} to that path ATOMICALLY (tempfile + fsync +
os.replace; see atomic_write_json) so a timeout mid-run can never commit
a truncated document.

The cycle FLIGHT RECORDER (kubetpu/utils/trace.py) is armed for the whole
run; the headline mode's span trees are committed as PIPELINE_TRACE.json
(flat span list, span_total) and PIPELINE_TRACE.perfetto.json (Chrome
traceEvents, loadable in ui.perfetto.dev — its ph:"X" count equals
span_total).  `make trace` / tools/traceview.py render the text flame
summary.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def build_world(n_nodes, n_pods, existing_per_node, store=None,
                ipa_heavy=False):
    from kubetpu.api import types as api
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow

    store = store or ClusterStore()
    nodes = hollow.make_nodes(n_nodes, zones=8)
    for i, n in enumerate(nodes):
        store.add(n)
        for p in hollow.make_pods(existing_per_node, prefix=f"ex-{i}-",
                                  group_labels=16):
            p.spec.node_name = n.name
            store.add(p)
    pending = hollow.make_pods(n_pods, prefix="pend-", group_labels=16)
    if ipa_heavy:
        # the 10k x 5k north-star case: EVERY pod carries topology terms
        # (BASELINE.md "InterPodAffinity-heavy"); zone affinity pulls the
        # app group together, hostname anti-affinity pushes replicas apart
        for i, p in enumerate(pending):
            if i % 2 == 0:
                hollow.with_anti_affinity(p, api.LABEL_HOSTNAME)
            else:
                hollow.with_affinity(p, api.LABEL_ZONE)
            if i % 3 == 0:
                hollow.with_spread(p, api.LABEL_ZONE, when="ScheduleAnyway")
    else:
        # topology work mixed in like scheduler_perf's blended configs:
        # 1/3 soft zone spread, 1/5 hostname anti-affinity on the app group
        for i, p in enumerate(pending):
            if i % 3 == 0:
                hollow.with_spread(p, api.LABEL_ZONE, when="ScheduleAnyway")
            if i % 5 == 0:
                hollow.with_anti_affinity(p, api.LABEL_HOSTNAME)
    return store, pending


def _percentile(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return xs[i]


def _median(xs):
    return _percentile(xs, 0.5)


def atomic_write_json(path, doc) -> None:
    """Crash-safe JSON write: tempfile in the target directory + flush +
    fsync + os.replace, so a reader (or a kill mid-run) never sees a
    truncated document — round-5's committed bench JSON was cut mid-file
    and unverifiable."""
    import tempfile
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, prefix=os.path.basename(path) + ".",
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def _achieved(stats, flops, seconds):
    """Achieved TFLOP/s over measured device seconds, plus the analytic
    MFU lower bound where the device has a peaks row (utils/flops) — an
    unknown device, the cpu backend included, gets no MFU field."""
    from kubetpu.utils.flops import device_peaks
    ach = flops / seconds
    stats["achieved_tflops"] = round(ach / 1e12, 2)
    peaks = device_peaks()
    if peaks is not None:
        stats["mfu_lower_bound"] = round(ach / peaks.flops_per_s, 4)


def _spread(raw):
    """min/median spread next to the best-of headline so a regression is
    distinguishable from run-to-run variance (warm attempts only — attempt 0
    pays compiles)."""
    if not raw:
        return {}
    return {"min_s": round(min(raw), 3),
            "median_s": round(_median(raw), 3),
            "max_s": round(max(raw), 3)}


def _slo_tracker():
    """The armed per-pod latency tracker (main() arms it for the whole
    run, next to the flight recorder), or None under a caller that did
    not arm it — every consumer degrades to no latency block."""
    from kubetpu.utils import slo as uslo
    return uslo.tracker()


def _devstats():
    """The armed device-side observability layer (main() arms it for
    the whole run), or None — every consumer degrades to no device
    block, exactly like the SLO tracker."""
    from kubetpu.utils import devstats as udevstats
    return udevstats.devstats()


def _measured_device_s(ds, program, cycles):
    """Estimated TOTAL device seconds a drain spent in ``program``:
    mean micro-fenced sample (kubetpu/utils/devstats.py deep-timing
    mode, every Nth cycle) x the drain's cycle count.  0.0 when devstats
    is disarmed or never sampled the program — callers fall back to the
    readback-block estimate (honest only unpipelined)."""
    if ds is None or not cycles:
        return 0.0
    mean = ds.mean_seconds(program)
    return mean * cycles if mean > 0 else 0.0


def _latency_block(trk):
    """The per-case per-pod ``latency`` block: e2e p50/p90/p99 (the SLO
    numbers — "100k pods x 10k nodes < 1 s p99" is judged on
    pod_e2e_p99_s) plus each stage's share of the total per-pod latency
    sum, the attribution vector tools/benchtrend.py diffs to name which
    stage a regression grew in.  None when the tracker is disarmed or
    saw no terminal pods."""
    if trk is None:
        return None
    stages = trk.stage_quantiles()
    e2e = stages.get("e2e")
    if not e2e or not e2e.get("count"):
        return None
    return {
        "pods": e2e["count"],
        "pod_e2e_p50_s": e2e.get("p50_s", 0.0),
        "pod_e2e_p90_s": e2e.get("p90_s", 0.0),
        "pod_e2e_p99_s": e2e.get("p99_s", 0.0),
        "pod_e2e_max_s": e2e.get("max_s", 0.0),
        "stage_p99_s": {name: st.get("p99_s", 0.0)
                        for name, st in stages.items()
                        if name != "e2e" and st.get("count")},
        "stage_shares": trk.shares(),
    }


def _rounds_hist(cycle_rounds):
    """Per-cycle auction round HISTOGRAM {rounds: cycles} — the shape of
    the round distribution, not just its max, so a windowing
    change that shifts the tail is visible in the committed JSON."""
    hist = {}
    for r in cycle_rounds:
        hist[str(int(r))] = hist.get(str(int(r)), 0) + 1
    return dict(sorted(hist.items(), key=lambda kv: int(kv[0])))


def run_mode(mode, n_nodes, n_pods, existing_per_node, repeats,
             mesh_shape=None, batch_cap=None, chain=None, ipa_heavy=False,
             pipeline=False, pipeline_depth=None):
    """One full e2e measurement: fresh store + scheduler per attempt; the
    first attempt pays XLA compiles (bounded by the persistent cache),
    later attempts reuse the in-process jit cache.  Pod counts above
    batch_cap drain over multiple cycles (per-cycle p50/p99 reported) —
    the serving loop's real shape."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.harness.perf import host_share
    from kubetpu.scheduler import Scheduler

    batch_cap = batch_cap or int(os.environ.get("BENCH_BATCH", "4096"))
    if chain is None:
        chain = os.environ.get("BENCH_CHAIN", "1") != "0"
    if pipeline_depth is None:
        pipeline_depth = 2          # the config default

    # compile vs cache-load split (PR 6 watchdog events, satellite of the
    # AOT PR): the jax.monitoring timer separates true XLA compile seconds
    # from persistent-cache deserialization, which first-minus-best wall
    # clock conflates (it went NEGATIVE on cache-warm runs)
    from kubetpu.utils.sanitize import CompileTimer, install_compile_timer
    timer = install_compile_timer()

    best = float("inf")
    first = None
    stats = None
    outcomes = sched = None
    raw_s = []            # every attempt's e2e seconds, in order
    compile_split = {}    # attempt 0's timer delta
    slo_trk = _slo_tracker()
    dev = _devstats()
    for attempt in range(repeats + 1):
        if sched is not None:
            sched.close()
        if slo_trk is not None:
            # the latency block describes the LAST attempt's drain (the
            # same attempt the stats dict survives from)
            slo_trk.clear()
        if dev is not None:
            # program samples reset per attempt (the ledger — what is
            # resident — survives clear(), like a real process)
            dev.clear()
        store, pending = build_world(n_nodes, n_pods, existing_per_node,
                                     ipa_heavy=ipa_heavy)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()],
            batch_size=min(n_pods, batch_cap), mode=mode,
            mesh_shape=mesh_shape, chain_cycles=chain,
            pipeline_cycles=pipeline, pipeline_depth=pipeline_depth)
        sched = Scheduler(store, config=cfg, async_binding=False)
        for p in pending:
            store.add(p)
        sched.device_wait_s = 0.0
        sched.device_flops = 0.0
        outcomes = []
        cycle_times = []
        cycle_rounds = []
        snap0 = timer.snapshot() if attempt == 0 else None
        t0 = time.time()
        while True:
            tc = time.time()
            out = sched.schedule_pending(timeout=0.2)
            if not out:
                break
            cycle_times.append(time.time() - tc)
            cycle_rounds.append(sched.last_gang_rounds)
            outcomes.extend(out)
        dt = time.time() - t0
        raw_s.append(round(dt, 3))
        if attempt == 0:
            first = dt
            compile_split = CompileTimer.delta(snap0, timer.snapshot())
        else:
            best = min(best, dt)
        stats = {
            "repeat_raw_s": list(raw_s),
            "spread": _spread(raw_s[1:]),   # warm attempts only
            "cycles": len(cycle_times),
            "cycle_p50_s": round(_percentile(cycle_times, 0.5), 3),
            "cycle_p99_s": round(_percentile(cycle_times, 0.99), 3),
            "device_wait_s": round(sched.device_wait_s, 3),
            "host_share": host_share(sched.device_wait_s, dt),
            # the executor depth this case drained at (1 = synchronous;
            # tools/benchtrend.py names depth changes when attributing
            # cross-round deltas) — and the mesh shape (None = single
            # device), named FIRST by the trend attribution: a
            # mesh_shape change is a config delta, not a regression
            "pipeline_depth": pipeline_depth if pipeline else 1,
            "mesh_shape": list(mesh_shape) if mesh_shape else None,
            # incremental tensorization (state/delta.py): rows the scatter
            # path updated per delta cycle + how often the blessed full
            # rebuild ran (last attempt's drain)
            "delta_rows_p50": _median(list(sched.delta_rows)),
            "resync_count": sched.resync_count,
        }
        latency = _latency_block(slo_trk)
        if latency is not None:
            stats["latency"] = latency
        if compile_split.get("compile_s", 0) or compile_split.get(
                "cache_load_s", 0):
            # measured split (overrides mode_summary's wall-clock
            # estimate); cache_load_s is the persistent-cache
            # deserialization share of attempt 0
            stats["compile_s"] = compile_split["compile_s"]
            stats["cache_load_s"] = compile_split["cache_load_s"]
        if mode == "gang":
            stats["auction_rounds_max"] = max(cycle_rounds, default=0)
            stats["auction_rounds_hist"] = _rounds_hist(cycle_rounds)
            # analytic matmul-FLOP lower bound (kubetpu/utils/flops.py):
            # achieved TFLOP/s over MEASURED device time when devstats is
            # armed (deep-timing fences, kubetpu/utils/devstats.py) —
            # honest at EVERY pipeline depth, since overlap can't hide
            # the fenced cycles.  Fallback: the readback-observed
            # device_wait_s, valid only unpipelined (overlap makes it a
            # lie, the pre-devstats refusal).
            stats["device_tflop"] = round(sched.device_flops / 1e12, 3)
            measured = _measured_device_s(dev, "run_auction",
                                          len(cycle_times))
            if measured > 0:
                stats["device_time_s"] = round(measured, 3)
                stats["device_time_source"] = "devstats"
                _achieved(stats, sched.device_flops, measured)
            elif sched.device_wait_s > 0 and not pipeline:
                stats["device_time_source"] = "device_wait"
                _achieved(stats, sched.device_flops, sched.device_wait_s)
        if dev is not None:
            # per-case device block: measured per-program device_time_s
            # + achieved-vs-roofline + residency-ledger totals
            stats["device"] = dev.summary()
    if repeats == 0:
        best = first
    return best, first, outcomes, sched, stats


def explain(sched, outcomes):
    """Attribute every unscheduled pod to its blocking filter(s) against the
    final cluster state (the state in which the last failures occurred)."""
    import jax

    from kubetpu.api import types as api
    from kubetpu.framework.types import PodInfo
    from kubetpu.models import programs
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder

    failed = [o.pod for o in outcomes if not o.node]
    if not failed:
        return {}
    sched.cache.update_snapshot(sched.snapshot)
    sb = SnapshotBuilder()
    pinfos = [PodInfo(p) for p in failed]
    sb.intern_pending(pinfos)
    cluster = sb.build(sched.snapshot.node_info_list).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    # attribute against the ACTIVE profile's filter list with the hostname
    # topo key (not the zone key) so attribution matches what actually
    # blocked scheduling
    fwk = next(iter(sched.profiles.values()))
    cfg = programs.ProgramConfig(
        filters=fwk.tensor_filters, scores=fwk.tensor_scores,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0),
        plugin_args=fwk.tensor_plugin_args(sb.table))
    no_feas, blocking = programs.explain_filters(cluster, batch, cfg)
    blocking = np.asarray(blocking)[:, :len(failed)]
    counts = {name: int(blocking[i].sum())
              for i, name in enumerate(cfg.filters) if blocking[i].any()}
    counts["_unschedulable"] = int(np.asarray(no_feas)[:len(failed)].sum())
    return counts


def compile_estimate(first, best):
    """First-run-minus-best is only a compile ESTIMATE; with the
    persistent XLA cache the first run can be the fastest (every compile
    is a cache load) and the raw subtraction went negative (BENCH_r05
    chain_on: -0.3).  This is the SINGLE fallback point where compile_s
    is computed from wall clock — every reporting path (headline modes,
    chain_drain's cases, northstar) flows through mode_summary and so
    through this clamp.  When run_mode's jax.monitoring CompileTimer saw
    events, its measured compile_s / cache_load_s split (which this
    estimate conflates) overrides the estimate via stats."""
    return round(max(first - best, 0.0), 1)


def _journal_armed() -> bool:
    """Whether the durable cycle journal rode this case's cycles —
    recorded in every case's JSON so a committed bench round states
    whether its numbers include journal-write overhead (normally False;
    replay_fidelity arms a private journal for its own drain)."""
    from kubetpu.utils import journal as ujournal
    return ujournal.journal() is not None


def mode_summary(mode, best, first, outcomes, sched, stats):
    scheduled = sum(1 for o in outcomes if o.node)
    d = {"e2e_best_s": round(best, 3),
         "first_run_s": round(first, 3),
         "compile_s": compile_estimate(first, best),
         "scheduled": scheduled,
         "journal_armed": _journal_armed(),
         "pods_per_sec": round(len(outcomes) / best, 1)}
    d.update(stats or {})
    if scheduled < len(outcomes):
        d["unscheduled_by_filter"] = explain(sched, outcomes)
    return d, len(outcomes) / best


def _gate_path(detail, dotted):
    cur = detail
    for part in dotted.split("."):
        if not isinstance(cur, dict):
            return None
        cur = cur.get(part)
    return cur if isinstance(cur, (int, float)) else None


def gate_entries(detail, northstar=None):
    """Build the NORTHSTAR.json "gate" section from a run's detail doc:
    dotted-path throughput metrics with a floor fraction derived from the
    recorded min/median warm spread (a current run below
    value * min_frac is a regression, not run-to-run variance).  Recorded by
    BENCH_FULL=1 runs; consumed by northstar_gate (BENCH_GATE=1).
    northstar: the BENCH_FULL shapes doc — adds the rescore_p99_s
    latency CEILING (the per-pod p99 the ROADMAP item 1 SLO is judged
    on; falls back to the per-cycle p99 on runs without the SLO layer
    armed)."""
    out = {}

    def rel_spread(spread):
        med, mn = spread.get("median_s"), spread.get("min_s")
        if not med or mn is None:
            return 0.15
        return max(0.05, (med - mn) / med)

    def entry(dotted, case):
        if case and case.get("pods_per_sec"):
            out[dotted] = {
                "pods_per_sec": case["pods_per_sec"],
                "min_frac": round(max(0.7, 1.0 - 2 * rel_spread(
                    case.get("spread", {}))), 3)}

    entry("gang.pods_per_sec", detail.get("gang"))
    cd = detail.get("chain_drain", {})
    for name in ("pipelined", "chain_on", "chain_off", "delta_sparse"):
        entry(f"chain_drain.{name}.pods_per_sec", cd.get(name))
    # node-flap storm throughput floor (the case has no warm repeat, so
    # the generous default min_frac from an empty spread applies)
    entry("node_flap.pods_per_sec", detail.get("node_flap"))
    # depth-k executor floors: the deepest measured ring must keep its
    # throughput (a regression here means the overlap stopped hiding
    # prepare/commit time behind device execution)
    pd = detail.get("pipeline_depth", {})
    for dkey in sorted(k for k in pd
                       if k.startswith("d") and k[1:].isdigit()):
        entry(f"pipeline_depth.{dkey}.pods_per_sec", pd.get(dkey))
    # cold_restart_s CEILING (lower is better, unlike the throughput
    # floors): restart-to-first-placement with AOT artifacts shipped.
    # The failure mode this catches is categorical — artifacts stop
    # hitting and the restart silently reverts to the trace path, a
    # 10x+ jump — so a generous 2x headroom absorbs run-to-run variance
    # without masking the regression
    wr = detail.get("warm_restart", {})
    if isinstance(wr.get("cold_restart_s"), (int, float)):
        out["warm_restart.cold_restart_s"] = {
            "seconds": wr["cold_restart_s"], "max_frac": 2.0}
    # rescore p99 latency CEILING (ROADMAP item 1's SLO axis): per-pod
    # e2e p99 when the SLO tracker was armed, per-cycle p99 otherwise.
    # The "path" field names the dotted detail location northstar_gate
    # reads the current run's value from (entries without it use their
    # own key as the path)
    rs = (northstar or {}).get("rescore_stream") or {}
    p99 = (rs.get("latency") or {}).get("pod_e2e_p99_s")
    path = "northstar.rescore_stream.latency.pod_e2e_p99_s"
    if not isinstance(p99, (int, float)):
        p99 = rs.get("cycle_p99_s")
        path = "northstar.rescore_stream.cycle_p99_s"
    if isinstance(p99, (int, float)) and p99 > 0:
        out["rescore_p99_s"] = {"seconds": round(p99, 3), "max_frac": 2.0,
                                "path": path}
    # sustained-load steady-state p99 CEILING (ROADMAP item 3's
    # open-loop axis): the windowed steady-state pod e2e p99 under the
    # seeded Poisson arrival stream, warmup excluded by the slope test
    # (utils/telemetry.py) — NOT a run-cumulative quantile
    sp = detail.get("sustained_load", {}).get("steady_p99_s")
    if isinstance(sp, (int, float)) and sp > 0:
        out["sustained_steady_p99_s"] = {
            "seconds": round(sp, 3), "max_frac": 2.0,
            "path": "sustained_load.steady_p99_s"}
    return out


def northstar_gate(detail, path="NORTHSTAR.json"):
    """BENCH_GATE=1 drift gate: compare this run's gang / chain_drain
    throughput against the floors recorded in NORTHSTAR.json's "gate"
    section and return the list of regressions (empty = pass).  Metrics
    missing on either side are skipped — a gate-less NORTHSTAR.json (or a
    run without the chain_drain case) passes vacuously, so the gate can
    ride every CI run and only bite after a BENCH_FULL re-anchor records
    floors for this backend."""
    failures = []
    # the serving-side bit-identity check rides the gate unconditionally
    # (no recorded floor needed): aot-artifact placements diverging from
    # the traced path is a correctness failure, not a perf regression
    if detail.get("warm_restart", {}).get("placements_match") is False:
        failures.append(
            "warm_restart: restart-mode placements diverged (cold / "
            "cache-warm / aot-artifact must be bit-identical)")
    # ...and for the pipeline depths: depth-1 is the synchronous oracle
    # the depth-k executor must reproduce bit-for-bit
    if detail.get("pipeline_depth", {}).get("placements_match") is False:
        failures.append(
            "pipeline_depth: depth-k placements diverged from the "
            "depth-1 synchronous drain (bit-identity contract, "
            "kubetpu/pipeline.py)")
    # ...and for the mesh: sharded placements diverging from the
    # unsharded drain is a correctness failure (the mesh is a
    # performance knob, never a semantics knob — parallel/shardmap.py)
    if detail.get("multichip_scale", {}).get("placements_match") is False:
        failures.append(
            "multichip_scale: sharded placements diverged from the "
            "unsharded drain (bit-identity contract, "
            "kubetpu/parallel/shardmap.py)")
    # ...and for the journal replay rig: a journaled drain must replay
    # to byte-identical placements (utils/journal.py + tools/kubereplay
    # — the same oracle discipline), and a pipelineDepth counterfactual
    # must be inert (depth never reaches a device program)
    rf = detail.get("replay_fidelity", {})
    if rf.get("bit_match") is False:
        failures.append(
            "replay_fidelity: journaled cycles did not replay to "
            "bit-identical placements (kubetpu/utils/journal.py + "
            "tools/kubereplay oracle)")
    if rf.get("counterfactual", {}).get(
            "pipeline_depth_divergent_cycles", 0):
        failures.append(
            "replay_fidelity: a pipelineDepth counterfactual changed "
            "placements — executor depth leaked into a device program")
    # the sustained-load steady-state contract rides the gate whenever
    # the case ran (no recorded floor needed): telemetry must be
    # write-only observability, the run must REACH steady state, and a
    # healthy stream admits no recovery demotions and completes what it
    # offers (coordinated-omission defense: the offered denominator is
    # the stream's, not the scheduler's)
    sl = detail.get("sustained_load", {})
    if sl and "error" not in sl:
        if sl.get("placements_match") is False:
            failures.append(
                "sustained_load: armed-vs-disarmed placements diverged "
                "(telemetry is write-only observability, "
                "kubetpu/utils/telemetry.py)")
        if ("steady_windows" in sl
                and int(sl.get("steady_windows") or 0) < 6):
            failures.append(
                f"sustained_load: only {int(sl.get('steady_windows') or 0)}"
                " steady-state windows (need >= 6 post-warmup windows "
                "passing the slope test)")
        if int(sl.get("demotions") or 0) > 0:
            failures.append(
                f"sustained_load: {int(sl.get('demotions') or 0)} recovery"
                "-ladder demotions during a healthy stream (must be 0)")
        cf = sl.get("completed_frac")
        if isinstance(cf, (int, float)) and cf < 0.95:
            failures.append(
                f"sustained_load: completed/offered = {cf} (must be "
                ">= 0.95 — the scheduler fell behind the open-loop "
                "offered rate)")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return failures
    for dotted, ref in sorted((doc.get("gate") or {}).items()):
        # an entry may carry an explicit dotted "path" (e.g. the
        # rescore_p99_s ceiling reads northstar.rescore_stream.*);
        # without one the key itself is the path
        cur = _gate_path(detail, ref.get("path", dotted))
        if cur is None:
            continue
        secs = ref.get("seconds")
        if secs:
            # seconds CEILING entry (cold_restart_s): lower is better
            ceiling = secs * ref.get("max_frac", 2.0)
            if cur > ceiling:
                failures.append(
                    f"{dotted}: {cur} s > ceiling {round(ceiling, 1)} "
                    f"(recorded {secs}, max_frac "
                    f"{ref.get('max_frac', 2.0)})")
            continue
        value = ref.get("pods_per_sec")
        if not value:
            continue
        floor = value * ref.get("min_frac", 0.85)
        if cur < floor:
            failures.append(
                f"{dotted}: {cur} pods/s < floor {round(floor, 1)} "
                f"(recorded {value}, min_frac {ref.get('min_frac', 0.85)})")
    return failures


def chain_drain_case(n_nodes, n_pods, existing_per_node):
    """Multi-cycle drain (batch_cap << n_pods): chaining ON reuses the
    previous cycle's materialized device cluster; OFF re-tensorizes the
    snapshot every cycle.  The VERDICT r3 ask: a measured number that
    justifies the feature (or its removal)."""
    out = {}
    cap = max(256, n_pods // 4)
    for label, chain, pipe in (("pipelined", True, True),
                               ("chain_on", True, False),
                               ("chain_off", False, False)):
        best, first, outcomes, sched, stats = run_mode(
            "gang", n_nodes, n_pods, existing_per_node, repeats=1,
            batch_cap=cap, chain=chain, pipeline=pipe)
        d, pods_per_sec = mode_summary("gang", best, first, outcomes, sched,
                                       stats)
        sched.close()
        d["pods_per_sec"] = round(pods_per_sec, 1)
        out[label] = d
    on, off = out["chain_on"], out["chain_off"]
    out["speedup"] = round(off["e2e_best_s"] / max(on["e2e_best_s"], 1e-9), 3)
    out["pipeline_speedup"] = round(
        on["e2e_best_s"] / max(out["pipelined"]["e2e_best_s"], 1e-9), 3)
    out["batch_cap"] = cap
    # the delta-tensorization target shape: SMALL waves against the full
    # cluster (chain OFF so every cycle exercises the scatter path) —
    # per-cycle churn is a handful of rows, exactly the case the
    # device-resident delta pipeline replaces the full rebuild for;
    # delta_rows_p50 / resync_count in the stats attribute the win
    try:
        best, first, outcomes, sched, stats = run_mode(
            "gang", n_nodes, max(128, n_pods // 8), existing_per_node,
            repeats=1, batch_cap=max(64, n_pods // 64), chain=False)
        d, pods_per_sec = mode_summary("gang", best, first, outcomes,
                                       sched, stats)
        sched.close()
        out["delta_sparse"] = d
    except Exception as e:  # pragma: no cover - depends on device state
        # never let the extra shape discard the three finished cases
        out["delta_sparse"] = {"error": repr(e)}
    return out


def pipeline_depth_case(n_nodes, n_pods, existing_per_node,
                        depths=(1, 2, 4)):
    """Depth-k pipelined executor (kubetpu/pipeline.py): the SAME
    deterministic serial-chain-bound world — the multi-cycle chained gang
    drain whose host_share motivated the refactor — drained once per
    pipeline depth.  Placements must be BIT-IDENTICAL across depths
    (every cycle dispatches against the previous cycle's speculative
    chain or the committed cache, never a divergent state); under
    BENCH_GATE a mismatch fails the run like warm_restart's
    placements_match, with no recorded floor needed.  The per-depth
    pods_per_sec / latency blocks record what the depth actually buys:
    deeper rings hide more prepare/commit time behind device execution
    (the stage_shares show which share shrank)."""
    out = {"depths": list(depths)}
    cap = max(256, n_pods // 8)
    placements = {}
    for depth in depths:
        best, first, outcomes, sched, stats = run_mode(
            "gang", n_nodes, n_pods, existing_per_node, repeats=1,
            batch_cap=cap, chain=True, pipeline=True, pipeline_depth=depth)
        d, pods_per_sec = mode_summary("gang", best, first, outcomes,
                                       sched, stats)
        d["pods_per_sec"] = round(pods_per_sec, 1)
        d["ring_high_water"] = sched._pipeline.ring.high_water
        placements[depth] = {o.pod.metadata.name: o.node for o in outcomes}
        sched.close()
        out[f"d{depth}"] = d
    out["batch_cap"] = cap
    base = placements[depths[0]]
    out["placements_match"] = bool(base) and all(
        placements[d] == base for d in depths)
    base_s = out[f"d{depths[0]}"]["e2e_best_s"]
    out["depth_speedup"] = {
        f"d{d}": round(base_s / max(out[f"d{d}"]["e2e_best_s"], 1e-9), 3)
        for d in depths[1:]}
    return out


def pv_heavy_case(n_nodes=1000, n_pods=2048):
    """PVC-heavy workload at >=1000 nodes (VERDICT r4 #4): every pod mounts
    a bound in-tree PV (zone-labeled, so VolumeZone really filters) plus a
    direct EBS volume (so the limits family counts).  The volume family
    runs as the device-side [B, N] mask (kubetpu/state/volumes.py); before
    it, this workload cost B x N Python filter calls per cycle."""
    import random

    from kubetpu.api import types as api
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.harness.perf import host_share
    from kubetpu.scheduler import Scheduler
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)

    def world():
        rng = random.Random(0)
        zones = [f"zone-{i}" for i in range(8)]
        store = ClusterStore()
        for n in hollow.make_nodes(n_nodes, zones=8):
            n.status.allocatable["attachable-volumes-aws-ebs"] = "39"
            store.add(n)
        pending = hollow.make_pods(n_pods, prefix="pv-", group_labels=16)
        for i, p in enumerate(pending):
            zone = rng.choice(zones)
            store.add(api.PersistentVolume(
                metadata=api.ObjectMeta(name=f"pv-{i}",
                                        labels={api.LABEL_ZONE: zone})))
            store.add(api.PersistentVolumeClaim(
                metadata=api.ObjectMeta(name=f"claim-{i}"),
                volume_name=f"pv-{i}"))
            p.spec.volumes = [
                api.Volume(name="data",
                           persistent_volume_claim=f"claim-{i}"),
                api.Volume(name="scratch",
                           aws_elastic_block_store=f"ebs-{i % 512}"),
            ]
        return store, pending

    best = None
    stats = {}
    sched = None
    raw_s = []
    for attempt in range(2):
        if sched is not None:
            sched.close()
        s2, pending = world()
        sched = Scheduler(s2, config=KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=n_pods,
            mode="gang", chain_cycles=True), async_binding=False)
        for p in pending:
            s2.add(p)
        sched.device_wait_s = 0.0
        t0 = time.time()
        outcomes = []
        while True:
            got = sched.schedule_pending(timeout=0.2)
            if not got:
                break
            outcomes.extend(got)
        dt = time.time() - t0
        raw_s.append(round(dt, 3))
        if best is None or dt < best:
            best = dt
            stats = {
                "nodes": n_nodes, "pods": n_pods,
                "e2e_best_s": round(dt, 3),
                "scheduled": sum(1 for o in outcomes if o.node),
                "device_wait_s": round(sched.device_wait_s, 3),
                "host_share": host_share(sched.device_wait_s, dt),
                "pipeline_depth": 1,
                "pods_per_sec": round(len(outcomes) / dt, 1),
            }
    stats["repeat_raw_s"] = raw_s
    stats["spread"] = _spread(raw_s[1:])
    stats["journal_armed"] = _journal_armed()
    sched.close()
    return stats


def node_flap_case(n_nodes=256, n_pods=1024, waves=4, flap=24):
    """Node-flap churn storm (ROADMAP item 5): between pod waves, `flap`
    nodes are deleted and re-added — the autoscaler add/remove pattern —
    so every wave's first cycle hits the DeltaTensorizer's node-set
    resync path while the drain keeps placing pods.  chain OFF so each
    cycle exercises the delta/resync machinery rather than the gang
    chain.  The schema carries resync_count + delta telemetry under the
    BENCH_GATE=1 drift gate: a recovery-path regression (resyncs
    exploding, or the storm cratering throughput) fails the run like any
    other floor."""
    import random

    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler

    rng = random.Random(0)
    slo_trk = _slo_tracker()
    if slo_trk is not None:
        slo_trk.clear()
    store = ClusterStore()
    nodes = hollow.make_nodes(n_nodes, zones=8)
    for n in nodes:
        store.add(n)
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()],
        batch_size=max(64, n_pods // waves), mode="gang",
        chain_cycles=False)
    sched = Scheduler(store, config=cfg, async_binding=False)
    sched.device_wait_s = 0.0
    outcomes = []
    cycle_times = []
    t0 = time.time()
    for wave in range(waves):
        for p in hollow.make_pods(n_pods // waves,
                                  prefix=f"flap-{wave}-"):
            store.add(p)
        while True:
            tc = time.time()
            got = sched.schedule_pending(timeout=0.2)
            if not got:
                break
            cycle_times.append(time.time() - tc)
            outcomes.extend(got)
        # the storm: rip `flap` random nodes out and bring them back —
        # bound pods ride through (the cache keeps their NodeInfo), and
        # the changed node set forces the blessed full resync
        victims = rng.sample(nodes, flap)
        for n in victims:
            store.delete(n)
        for n in victims:
            store.add(n)
    dt = time.time() - t0
    scheduled = sum(1 for o in outcomes if o.node)
    stats = {
        "nodes": n_nodes, "pods": len(outcomes), "waves": waves,
        "flap_per_wave": flap,
        "e2e_s": round(dt, 3),
        "cycles": len(cycle_times),
        "cycle_p50_s": round(_percentile(cycle_times, 0.5), 3),
        "cycle_p99_s": round(_percentile(cycle_times, 0.99), 3),
        "device_wait_s": round(sched.device_wait_s, 3),
        "scheduled": scheduled,
        "pipeline_depth": 1,
        "pods_per_sec": round(len(outcomes) / max(dt, 1e-9), 1),
        # the recovery-path telemetry this case exists to record
        "resync_count": sched.resync_count,
        "delta_rows_p50": _median(list(sched.delta_rows)),
        "recoveries": len(sched.recovery_log),
        "journal_armed": _journal_armed(),
    }
    latency = _latency_block(slo_trk)
    if latency is not None:
        stats["latency"] = latency
    sched.close()
    return stats


def preemption_case(n_nodes=500, fillers=2000, high_prio=256):
    """Preemption under load (VERDICT r4 #9): the cluster is packed with
    low-priority fillers (4 x 900m per 4-cpu node), then high-priority
    600m pods arrive — every placement must select victims through the
    PostFilter preemption WAVE (eligibility, one batched [B, C, K]
    what-if per cycle, contention auction, ranked commit).  Warm
    best-of-2 like the other cases (attempt 0 pays the compiles), with
    the per-attempt cycle count and device-wait/host split reported."""
    from kubetpu.harness.perf import Workload, run_workload
    best = None
    raw = []       # per-attempt average preempting pods/s, in order
    for attempt in range(2):
        t0 = time.time()
        items = run_workload(Workload(
            name="PreemptionBench", num_nodes=n_nodes,
            num_init_pods=fillers, num_pods_to_schedule=high_prio,
            preemption=True, batch_size=1024, timeout_s=420))
        dt = time.time() - t0
        thr = next(it.data for it in items
                   if it.labels.get("Metric") == "SchedulingThroughput")
        stats = next((it.data for it in items
                      if it.labels.get("Metric") == "SchedulerStats"), {})
        cur = {"nodes": n_nodes, "fillers": fillers, "high_prio": high_prio,
               "e2e_s": round(dt, 1),
               "first_attempt": attempt == 0,
               "cycles": int(stats.get("Cycles", 0)),
               "device_wait_s": stats.get("DeviceWaitS", 0.0),
               "host_share": stats.get("HostShare", 0.0),
               "preempting_pods_per_sec": thr}
        raw.append(round(thr.get("Average", 0.0), 2))
        if (best is None or thr.get("Average", 0.0)
                > best["preempting_pods_per_sec"].get("Average", 0.0)):
            best = cur
    if best is not None:
        best["repeat_raw_pods_per_sec"] = raw
        warm = raw[1:] or raw
        best["spread"] = {"min": min(warm), "median": _median(warm),
                          "max": max(warm)}
        best["journal_armed"] = _journal_armed()
    return best


def replay_fidelity_case(n_nodes=12, n_pods=240, batch=8, depth=4):
    """Durable-journal replay oracle (kubetpu/utils/journal.py +
    tools/kubereplay): a deterministic heterogeneous world — mixed node
    capacities and zones, 1/3 of pods carrying soft zone spread so the
    score plugins genuinely disagree — is drained at pipeline depth 4
    with mid-drain node churn (chain breaks -> delta cycles + resyncs),
    journaled to a private directory, and replayed IN-PROCESS:

      * bit_match: every journaled cycle must replay to a byte-identical
        packed placement vector.  Under BENCH_GATE=1 a mismatch fails
        the run like warm_restart's placements_match — bit-identity is
        correctness, no recorded floor needed.
      * counterfactual: the SAME window re-run with PodTopologySpread's
        score weight zeroed must report NONZERO placement divergence
        (the eval-set axis works), while a pipelineDepth change must
        report ZERO (executor depth never reaches a device program) —
        both recorded, the depth check gated."""
    import copy
    import shutil
    import tempfile

    from kubetpu.api import types as api
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import journal as ujournal
    from tools.kubereplay import replay_journal

    work = tempfile.mkdtemp(prefix="kubetpu-journal-")
    ujournal.disarm_journal()
    jr = ujournal.arm_journal(work)
    sched = None
    try:
        store = ClusterStore()
        nodes = []
        for i in range(n_nodes):
            n = hollow.make_node(f"jr-node-{i}", zone=f"zone-{i % 3}",
                                 region="region-0",
                                 cpu_milli=8000 if i % 2 else 3000)
            nodes.append(n)
            store.add(n)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=batch,
            mode="gang", chain_cycles=True, pipeline_cycles=True,
            pipeline_depth=depth)
        sched = Scheduler(store, config=cfg, async_binding=False)
        for i, p in enumerate(hollow.make_pods(n_pods, prefix="jr-",
                                               group_labels=4,
                                               cpu_milli=150)):
            if i % 3 == 0:
                hollow.with_spread(p, api.LABEL_ZONE,
                                   when="ScheduleAnyway")
            store.add(p)
        outcomes = []
        i = 0
        t0 = time.time()
        while True:
            got = sched.schedule_pending(timeout=0.0)
            if not got:
                break
            outcomes.extend(got)
            i += 1
            if i % 7 == 0:
                # external node churn: chain break -> delta/resync path
                n = copy.deepcopy(nodes[i % len(nodes)])
                n.metadata.labels["flap"] = f"v{i}"
                store.update(n)
        outcomes.extend(sched.flush_pipeline())
        drain_s = time.time() - t0
        t1 = time.time()
        rep = replay_journal(work)
        replay_s = time.time() - t1
        cf_w = replay_journal(work, counterfactual={
            "score_weights": {"PodTopologySpread": 0}})["counterfactual"]
        cf_d = replay_journal(work, counterfactual={
            "pipeline_depth": depth * 2})["counterfactual"]
        out = {
            "nodes": n_nodes, "pods": len(outcomes),
            "scheduled": sum(1 for o in outcomes if o.node),
            "cycles": sched.cycle_count,
            "pipeline_depth": depth,
            "drain_s": round(drain_s, 3),
            "replay_s": round(replay_s, 3),
            "records": rep["records"],
            "replayed": rep["replayed"],
            "skipped": len(rep["skipped"]),
            "journal_bytes": jr.disk_bytes(),
            "journal_armed": True,
            # the gated oracle (northstar_gate, like placements_match)
            "bit_match": rep["bit_match"] is True,
            "counterfactual": {
                "score_weight_divergent_cycles":
                    cf_w["divergent_cycles"],
                "score_weight_pods_moved": cf_w["diverged_pods"],
                "utilization_delta": cf_w["utilization"]["delta"],
                # must be 0 — depth never reaches a device program
                "pipeline_depth_divergent_cycles":
                    cf_d["divergent_cycles"],
            },
        }
        if rep["first_divergence"] is not None:
            out["first_divergence"] = rep["first_divergence"]["seq"]
        return out
    finally:
        if sched is not None:
            sched.close()
        ujournal.disarm_journal()
        shutil.rmtree(work, ignore_errors=True)


def sustained_load_case(n_nodes=64, rate=None, duration_s=None,
                        window_s=None):
    """Sustained open-loop load with steady-state telemetry (ROADMAP
    item 3's arrival-process axis): a seeded Poisson arrival stream
    (kubetpu/harness/hollow.py) is fired at its wall deadlines against a
    live serving scheduler (harness/perf.py SustainedLoadRunner — the
    coordinated-omission defense: offered rate fixed by the stream,
    completed rate measured separately), while the windowed telemetry
    ring (kubetpu/utils/telemetry.py) records per-window e2e quantiles.
    The verdict is the STEADY-STATE windowed p99 — warmup cut by the
    slope test, never averaged in.

    Two phases, both gated under BENCH_GATE=1:
      1. parity — the same seeded stream drained synchronously with the
         ring armed vs disarmed must produce bit-identical placements
         (telemetry is write-only observability, never a policy input);
      2. measured — after a short warmup drain pays the compiles, the
         open-loop stream runs for duration_s with window_s-second
         telemetry windows.  The gate demands >= 6 steady-state windows,
         ZERO recovery-ladder demotions, and offered-vs-completed within
         5%; the steady p99 lands in NORTHSTAR.json as a seconds
         ceiling."""
    from kubetpu.api import types as kapi
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.harness.perf import SustainedLoadRunner
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import telemetry as utelemetry

    rate = float(os.environ.get("BENCH_SUSTAINED_RATE", rate or 8.0))
    duration_s = float(os.environ.get("BENCH_SUSTAINED_S",
                                      duration_s or 12.0))
    window_s = float(os.environ.get("BENCH_SUSTAINED_WINDOW",
                                    window_s or 1.0))

    # The measured stream is seeded, so its exact add count is known
    # up front — sizing below is exact, not statistical
    warm_sizes = (1, 2, 4, 8, 16, 32)
    events = hollow.poisson_stream(rate, duration_s, seed=11)
    n_meas = sum(1 for e in events if e["kind"] == "add")
    # pod-axis pow2 ceiling: fill pins the bucket (fill+1 must already
    # pad to it), and BOTH the warmup drip (warm pods resident) and the
    # measured stream (warm pods deleted) must finish under it.  Keeping
    # the ceiling SMALL matters as much as not crossing it: bucket-2048
    # programs cost seconds per dispatch on CPU, stretching the
    # tick-piggybacked windows until the slope test can never converge.
    need = max(n_meas + 16, sum(warm_sizes) + 32) + 8
    ceil_pow = 1 << (2 * need - 1).bit_length()
    fill = ceil_pow // 2 + 8

    def make_world(fill=0):
        store = ClusterStore()
        nodes = hollow.make_nodes(n_nodes, zones=8)
        for n in nodes:
            store.add(n)
        # bound filler pods enter the cluster tensor WITHOUT being
        # scheduled: they pin the pod-axis pow2 pad bucket above the
        # range warmup + stream traverse, so the measured phase never
        # pays a mid-run bucket recompile (the stall class
        # Scheduler._prewarm_ladder exists for, contained statically —
        # every program the open-loop cycles need is compiled before
        # the first measured window)
        for i in range(fill):
            p = hollow.make_pod(f"fill-{i}",
                                labels={"app": f"app-{i % 16}"})
            # heavier spread share than the stream (25%): the fill
            # pins the TERM-axis pad bucket too, so stream spread pods
            # can't grow the constraint surface across a pow2 edge
            if i % 2 == 0:
                hollow.with_spread(p, kapi.LABEL_ZONE,
                                   when="ScheduleAnyway")
            p.spec.node_name = nodes[i % len(nodes)].name
            store.add(p)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()],
            batch_size=256, mode="gang", chain_cycles=False)
        return store, cfg

    # -- phase 1: armed-vs-disarmed parity on a deterministic drain.
    # The stream is regenerated from the same seed per run (binding
    # mutates pod.spec.node_name in place, so the two drains must not
    # share pod objects); open-loop timing is nondeterministic, so
    # parity uses synchronous injection of the identical pod set.
    def parity_drain(arm):
        # arm_telemetry is idempotent (returns any existing ring), so
        # drop the bench-global 5 s ring before arming at a tick-heavy
        # 50 ms window
        utelemetry.disarm_telemetry()
        if arm:
            utelemetry.arm_telemetry(window_s=0.05)
        try:
            store, cfg = make_world()
            sched = Scheduler(store, config=cfg, async_binding=False)
            sched.device_wait_s = 0.0
            for e in hollow.poisson_stream(rate, 8.0, seed=7):
                if e["kind"] == "add":
                    store.add(e["pod"])
            placements = {}
            while True:
                got = sched.schedule_pending(timeout=0.2)
                if not got:
                    break
                for o in got:
                    placements[o.pod.metadata.name] = o.node
            sched.close()
            return placements
        finally:
            utelemetry.disarm_telemetry()

    p_armed = parity_drain(True)
    p_plain = parity_drain(False)
    parity = bool(p_armed) and p_armed == p_plain

    # -- phase 2: the measured open-loop run.  The SLO tracker resets
    # FIRST so its cumulative stage shares (the latency block benchtrend
    # attributes regressions to) describe this case alone; the fresh
    # ring is armed after, so its first window's delta baseline is the
    # cleared tracker
    slo_trk = _slo_tracker()
    if slo_trk is not None:
        slo_trk.clear()
    store, cfg = make_world(fill=fill)
    utelemetry.disarm_telemetry()
    utelemetry.arm_telemetry(window_s=window_s)
    sched = Scheduler(store, config=cfg, async_binding=True)
    sched.run()                 # base prewarm rides startup (run())
    try:
        # warmup drip: the live serving loop pays each pow2
        # incoming-batch bucket (1..32) the open-loop cycles will hit —
        # one group at a time, each bound before the next is offered —
        # so the measured stream meets only compiled programs and the
        # steady-state slope test converges inside a CPU-scale run.
        # Warmup windows stay in the ring; the slope test cuts them.
        warm_pool = [e["pod"] for e in hollow.poisson_stream(
            rate, 4.0 * sum(warm_sizes) / rate, seed=3, prefix="warm-")
            if e["kind"] == "add"]
        warm = []
        t_warm = time.time()
        deadline = t_warm + 300.0
        for k in warm_sizes:
            if len(warm_pool) < len(warm) + k:
                break
            group = warm_pool[len(warm):len(warm) + k]
            for p in group:
                store.add(p)
            warm.extend(group)
            while time.time() < deadline:
                if all((store.get_pod(p.namespace, p.metadata.name)
                        or p).spec.node_name for p in group):
                    break
                time.sleep(0.05)
        # warm pods leave before the measured phase so the stream's
        # arrivals refill the same pod-count range the drip traversed —
        # fill + n_meas stays under ceil_pow and the pod-axis bucket
        # never moves
        for p in warm:
            cur = store.get_pod(p.namespace, p.metadata.name)
            if cur is not None:
                store.delete(cur)
        warm_s = time.time() - t_warm
        res = SustainedLoadRunner(store, sched, events, duration_s,
                                  settle_s=30.0).run()
    finally:
        sched.close()
        utelemetry.disarm_telemetry()

    load = res.get("load") or {}
    steady = load.get("steady") or {}
    out = {
        "nodes": n_nodes, "rate": rate, "window_s": window_s,
        "stream": "poisson", "fill_pods": fill,
        "warmup_pods": len(warm), "warmup_s": round(warm_s, 2),
        "placements_match": parity,
        # the gate quartet: steady span, steady p99 (ceiling), zero
        # demotions, offered-vs-completed
        "steady_windows": int(steady.get("windows", 0)),
        "steady_p99_s": steady.get("p99_s"),
        "steady_p50_s": steady.get("p50_s"),
        "demotions": int(load.get("demotions", 0)),
        "journal_armed": _journal_armed(),
    }
    latency = _latency_block(slo_trk)
    if latency is not None:
        out["latency"] = latency
    out.update(res)
    return out


def _restart_once(n_nodes, existing_per_node, wave, ladder, timer):
    """ONE simulated restart: fresh deterministic world (the SAME
    hollow.restart_world/restart_wave builders tools/kubeaot build_shape
    captures from — that shared construction is what makes the aot
    signature lookup hit), fresh Scheduler, prewarm, then the wave's
    first cycle.  Caller controls what "fresh process" means by clearing
    jax's in-process caches and choosing the persistent-cache /
    aot-artifact state beforehand."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler

    snap = timer.snapshot()
    store = hollow.restart_world(n_nodes, existing_per_node=existing_per_node)
    t0 = time.time()
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=wave, mode="gang",
        chain_cycles=True), async_binding=False)
    sched.prewarm(ladder_steps=ladder)
    prewarm_s = time.time() - t0
    for p in hollow.restart_wave(wave):
        store.add(p)
    t1 = time.time()
    out = sched.schedule_pending(timeout=1.0)
    first_cycle_s = time.time() - t1
    placements = sorted((o.pod.metadata.name, o.node) for o in out)
    from kubetpu.utils.sanitize import CompileTimer
    split = CompileTimer.delta(snap, timer.snapshot())
    stats = {
        "prewarm_s": round(prewarm_s, 2),
        "first_cycle_s": round(first_cycle_s, 3),
        # restart cost to FIRST COMMITTED PLACEMENT — the fleet
        # availability number the cold_restart_s gate tracks
        "restart_s": round(prewarm_s + first_cycle_s, 3),
        "compile_s": split.get("compile_s", 0.0),
        "cache_load_s": split.get("cache_load_s", 0.0),
        "scheduled": sum(1 for o in out if o.node),
        "ladder_buckets": [list(x) for x in sched.prewarm_report],
    }
    sched.close()
    return stats, placements


def warm_restart_case(n_nodes=1000, existing_per_node=2, wave=1024,
                      ladder=2):
    """Restart SLO (VERDICT r4 #5 / ROADMAP open item 2), measured in the
    THREE restart modes a fleet can deploy in — this runs first in main()
    so the process has run no jit yet:

    * "cold": empty persistent cache — every program pays a true XLA
      compile (what first_run_s showed at 133-737 s on the north-star
      shapes).
    * "cache_warm": the persistent compilation cache populated by the
      cold run — each program still pays trace + lower, but the backend
      compile is a disk load (compile_s ~0, cache_load_s > 0).
    * "aot_artifact": build-time serialized executables (tools/kubeaot
      --shape) deserialize-and-loaded by Scheduler.prewarm — no trace, no
      lower, no XLA; the first cycle's dispatch hits resident
      executables by call signature.

    jax.clear_caches() between modes simulates the process restart (the
    in-process jit cache is dropped; only the on-disk state differs).
    The three modes schedule the SAME deterministic world and wave, and
    placements must be BIT-IDENTICAL across them — the aot path runs the
    same StableHLO the traced path lowers (manifest hash equality is the
    build-time oracle; this is the serving-side check)."""
    import jax

    from kubetpu.utils import aot
    from kubetpu.utils.compilation import cache_subdir
    from kubetpu.utils.sanitize import install_compile_timer

    timer = install_compile_timer()
    out = {"nodes": n_nodes, "wave": wave}
    modes = {}
    # a PRIVATE empty persistent cache for the whole case, at a fixed
    # sub-path of the active cache root: "cold" is cold even when the
    # root has entries, and "cache_warm" loads exactly what the cold run
    # compiled
    with cache_subdir("warm-restart") as work:
        aot_dir = os.path.join(work, "aot")
        jax.clear_caches()
        modes["cold"], p_cold = _restart_once(
            n_nodes, existing_per_node, wave, ladder, timer)
        jax.clear_caches()
        modes["cache_warm"], p_warm = _restart_once(
            n_nodes, existing_per_node, wave, ladder, timer)
        # build the artifact set the way a deploy pipeline would
        # (tools/kubeaot --shape NxB, a fresh process): captures compile
        # FRESH (the build disables the persistent cache — a cache-hit
        # executable re-serializes unloadably) and the in-process caches
        # are dropped first so earlier modes' compiled kernels can't
        # dedup symbols out of the new executables
        from tools.kubeaot.build import build_shape
        jax.clear_caches()
        t0 = time.time()
        build = build_shape(aot_dir, n_nodes, wave, ladder=ladder,
                            existing_per_node=existing_per_node)
        build_s = time.time() - t0
        jax.clear_caches()
        aot.arm(aot.serve_runtime(aot_dir))
        try:
            modes["aot_artifact"], p_aot = _restart_once(
                n_nodes, existing_per_node, wave, ladder, timer)
        finally:
            rt = aot.active_runtime()
            aot_stats = rt.stats() if rt is not None else {}
            aot.disarm()
        modes["aot_artifact"]["aot"] = aot_stats
        modes["aot_artifact"]["build_s"] = round(build_s, 2)
        modes["aot_artifact"]["artifact_rows"] = build.get("rows")
        out["modes"] = modes
        out["placements_match"] = (p_cold == p_warm == p_aot)
        out["journal_armed"] = _journal_armed()
        # the gated number: restart-to-first-placement with artifacts
        # shipped — what a rolling fleet restart actually costs
        out["cold_restart_s"] = modes["aot_artifact"]["restart_s"]
        out["aot_speedup_vs_cold"] = round(
            modes["cold"]["restart_s"]
            / max(modes["aot_artifact"]["restart_s"], 1e-9), 1)
    return out


def rescore_case(n_pods=51200, n_nodes=10240, chunk=4096):
    """North star: STREAMING drain toward 100k x 10k (BASELINE.md
    "autoscaler simulate") — with HONEST semantics (VERDICT r4 #3): every
    chunk is DISTINCT pods, per-chunk tensorize is on the clock, and
    placements COMMIT between chunks so capacity and topology counts
    evolve (pods in chunk k see chunks < k exactly as the serial scheduler
    would).  This is simply the full serving path: store -> queue ->
    pipelined chained gang drain in `chunk`-pod cycles, one packed
    readback per cycle.

    The existing-pod axis genuinely grows to ~n_pods by the end — that is
    the honest physics of a cluster that ends the drain with every pod
    bound.  The SINGLE-CHIP scale cap is HBM: at ~131k committed pods x
    16k node slots the dense topology state (pod label one-hots + the
    [P, N] same-pair matmul operands) exceeds the chip, so the default
    here is 51200 x 10240 (P <= 65536) and the stated path to the full
    100k x 10k < 1 s p99 target is the v5e-8 mesh (parallel/mesh.py
    shards the pod axis 8x, dryrun-compiled by __graft_entry__), which
    divides both the HBM residency and the per-round matmul time."""
    import jax

    from kubetpu.scheduler import Scheduler
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)

    out = {}
    first_e2e = None
    raw_s = []
    slo_trk = _slo_tracker()
    for attempt in range(2):   # attempt 0 pays the P-bucket compile ladder
        if slo_trk is not None:
            slo_trk.clear()
        if _devstats() is not None:
            _devstats().clear()
        store, pending = build_world(n_nodes, n_pods, existing_per_node=1)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=chunk, mode="gang",
            chain_cycles=True, pipeline_cycles=True,
            pipeline_depth=int(os.environ.get("BENCH_RESCORE_DEPTH", "2")))
        sched = Scheduler(store, config=cfg, async_binding=False)
        for p in pending:
            store.add(p)
        sched.device_wait_s = 0.0
        sched.device_flops = 0.0
        outcomes = []
        cycle_times = []
        t0 = time.time()
        while True:
            tc = time.time()
            got = sched.schedule_pending(timeout=0.2)
            if not got:
                break
            cycle_times.append(time.time() - tc)
            outcomes.extend(got)
        dt = time.time() - t0
        raw_s.append(round(dt, 3))
        scheduled = sum(1 for o in outcomes if o.node)
        mem = jax.local_devices()[0].memory_stats() or {}
        if attempt == 0:
            first_e2e = dt
        out = {
            "repeat_raw_s": list(raw_s),
            "spread": _spread(raw_s[1:]),
            "pods": n_pods, "nodes": n_nodes, "chunk": chunk,
            "semantics": "distinct pods/chunk, tensorize on-clock, "
                         "placements committed between chunks",
            "path_to_target": "v5e-8 mesh shards the pod axis 8x "
                              "(parallel/mesh.py); single chip caps at "
                              "~64k committed pods x 16k node slots",
            "e2e_s": round(dt, 3),
            "first_run_s": round(first_e2e, 3),
            "cycles": len(cycle_times),
            "cycle_p50_s": round(_percentile(cycle_times, 0.5), 3),
            "cycle_p99_s": round(_percentile(cycle_times, 0.99), 3),
            "device_wait_s": round(sched.device_wait_s, 3),
            "device_tflop": round(sched.device_flops / 1e12, 3),
            "pipeline_depth": cfg.pipeline_depth,
            "pods_per_sec": round(len(outcomes) / dt, 1),
            "scheduled": scheduled,
            "hbm_peak_bytes": int(mem.get("peak_bytes_in_use", 0)),
            "journal_armed": _journal_armed(),
        }
        dev = _devstats()
        measured = _measured_device_s(dev, "run_auction",
                                      len(cycle_times))
        if measured > 0:
            # the pipelined rescore previously reported no achieved
            # FLOP/s at all (overlap corrupted device_wait_s); measured
            # device time restores the number at any depth
            out["device_time_s"] = round(measured, 3)
            out["device_time_source"] = "devstats"
            _achieved(out, sched.device_flops, measured)
        if dev is not None:
            out["device"] = dev.summary()
        latency = _latency_block(slo_trk)
        if latency is not None:
            out["latency"] = latency
        if scheduled < len(outcomes):
            out["unscheduled"] = len(outcomes) - scheduled
        sched.close()
    return out


def multichip_scale_case(mesh_shape, n_nodes=512, n_pods=2048,
                         existing_per_node=1, batch_cap=512):
    """Pod-axis mesh scale-out (ROADMAP item 1): the SAME deterministic
    north-star-SHAPED world — term-free pending pods, the tiled
    shard_map auction's supported surface, drained in chained pipelined
    cycles — run once unsharded and once on the virtual-CPU mesh.
    Placements must be BIT-IDENTICAL (under BENCH_GATE a mismatch fails
    the run like warm_restart's, no recorded floor needed: the mesh is a
    performance knob, never a semantics knob).  On CPU the mesh seconds
    carry no perf claim (8 virtual devices share the host); the JSON
    records what a TPU run gates on — pod_e2e_p99_s, the per-shard
    devstats device block + HBM split, and whether the double-buffered
    batch upload actually overlapped the previous wave's device window
    (flight-recorder span intersection)."""
    import jax

    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import devstats as udevstats
    from kubetpu.utils import trace as utrace

    def run(shape):
        dev = _devstats()
        if dev is not None:
            dev.clear()
        slo_trk = _slo_tracker()
        if slo_trk is not None:
            slo_trk.clear()
        store = ClusterStore()
        for i, n in enumerate(hollow.make_nodes(n_nodes, zones=8)):
            store.add(n)
            for p in hollow.make_pods(existing_per_node, prefix=f"ex-{i}-",
                                      group_labels=16):
                p.spec.node_name = n.name
                store.add(p)
        # group_labels=0: term-free pending pods — needs_topo routes
        # intra_batch_topology=False, so the mesh run takes the TILED
        # gather-free shard_map auction (parallel/shardmap.py)
        pending = hollow.make_pods(n_pods, prefix="pend-", group_labels=0)
        cfg = KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()],
            batch_size=min(n_pods, batch_cap), mode="gang",
            mesh_shape=shape, chain_cycles=True, pipeline_cycles=True,
            pipeline_depth=2)
        sched = Scheduler(store, config=cfg, async_binding=False)
        for p in pending:
            store.add(p)
        placements = {}
        cycle_times = []
        rounds = []
        t0 = time.time()
        while True:
            tc = time.time()
            out = sched.schedule_pending(timeout=0.2)
            if not out:
                break
            cycle_times.append(time.time() - tc)
            rounds.append(sched.last_gang_rounds)
            for o in out:
                placements[o.pod.metadata.name] = o.node
        dt = time.time() - t0
        stats = {
            "mesh_shape": list(shape) if shape else None,
            "e2e_s": round(dt, 3),
            "cycles": len(cycle_times),
            "cycle_p50_s": round(_percentile(cycle_times, 0.5), 3),
            "cycle_p99_s": round(_percentile(cycle_times, 0.99), 3),
            "pods_per_sec": round(len(placements) / max(dt, 1e-9), 1),
            "placed": sum(1 for v in placements.values() if v),
            "auction_rounds_hist": _rounds_hist(rounds),
            "journal_armed": _journal_armed(),
        }
        latency = _latency_block(slo_trk)
        if latency is not None:
            stats["latency"] = latency
        if dev is not None:
            # the per-shard device block: measured program seconds +
            # the residency ledger split across the mesh (the ledger
            # registers GLOBAL bytes; each shard holds 1/shards of every
            # node/pod-axis table — exactly devstats.project's model)
            stats["device"] = dev.summary()
            if shape:
                shards = int(shape[0]) * int(shape[1])
                ledger = dev.ledger()
                total = int(ledger.get("total_bytes", 0))
                stats["per_shard"] = {
                    "shards": shards,
                    "hbm_bytes_per_shard": int(total // max(shards, 1)),
                    "northstar_hbm_projection": udevstats.project(
                        ledger, 10000, 100000, shards=shards,
                        groups=("delta-resident", "chain")),
                }
        if shape:
            # double-buffer visibility: a "batch-upload" span (issued in
            # prepare, parallel/mesh-bound device_put) counts as
            # OVERLAPPED when it starts inside another cycle's
            # dispatch->readback window — the wave whose auction the
            # transfer rode behind
            rec = utrace.flight_recorder()
            if rec is not None:
                doc = rec.to_pipeline_doc(workload="multichip_scale")
                spans = doc.get("spans", [])
                windows = {}
                for s in spans:
                    if s["stage"] == "dispatch":
                        w = windows.setdefault(s["cycle"], [None, None])
                        w[0] = s["start_s"]
                    elif s["stage"] == "packed-readback":
                        w = windows.setdefault(s["cycle"], [None, None])
                        w[1] = s["end_s"]
                ups = [s for s in spans if s["stage"] == "batch-upload"]
                overlapped = sum(
                    1 for s in ups
                    if any(w[0] is not None and w[1] is not None
                           and w[0] <= s["start_s"] <= w[1]
                           for c, w in windows.items()
                           if c != s["cycle"]))
                stats["batch_upload"] = {
                    "spans": len(ups),
                    "overlapped_prev_device_window": overlapped,
                    "double_buffered": True,
                }
        sched.close()
        return placements, stats

    p_ref, s_ref = run(None)
    p_mesh, s_mesh = run(tuple(mesh_shape))
    return {"nodes": n_nodes, "pods": n_pods,
            "mesh_shape": list(mesh_shape),
            "backend": jax.default_backend(),
            "unsharded": s_ref, "sharded": s_mesh,
            "pod_e2e_p99_s": (s_mesh.get("latency") or {}).get(
                "pod_e2e_p99_s"),
            "northstar_hbm_projection": (s_mesh.get("per_shard") or {}).get(
                "northstar_hbm_projection"),
            "placements_match": bool(p_ref) and p_ref == p_mesh}


def errored_cases(doc, path=""):
    """Paths of every case (at any depth) that recorded {"error": ...}
    instead of a result."""
    if not isinstance(doc, dict):
        return []
    if "error" in doc:
        return [path]
    out = []
    for key, val in doc.items():
        out += errored_cases(val, f"{path}.{key}" if path else key)
    return out


def main() -> None:
    n_nodes = int(os.environ.get("BENCH_NODES", "1000"))
    n_pods = int(os.environ.get("BENCH_PODS", "4096"))
    existing_per_node = int(os.environ.get("BENCH_EXISTING_PER_NODE", "2"))
    repeats = int(os.environ.get("BENCH_REPEATS", "2"))
    modes = os.environ.get("BENCH_MODES", "gang,sequential").split(",")
    full = os.environ.get("BENCH_FULL", "0") == "1"

    mesh_shape = None
    if os.environ.get("BENCH_MESH"):
        mesh_shape = tuple(int(x) for x in
                           os.environ["BENCH_MESH"].split(","))
        # on the CPU backend (JAX_PLATFORMS=cpu), make sure enough virtual
        # devices exist before jax initializes; REPLACE any smaller
        # pre-existing device-count flag.  The flag does nothing on a TPU
        # host: there the mesh must fit the real chips or make_mesh raises
        need = mesh_shape[0] * mesh_shape[1]
        flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
                 if "xla_force_host_platform_device_count" not in f]
        flags.append(f"--xla_force_host_platform_device_count={need}")
        os.environ["XLA_FLAGS"] = " ".join(flags)

    from kubetpu.utils.compilation import enable_persistent_cache
    enable_persistent_cache()
    # BENCH_GATE=1: observe every XLA compile event for the census
    # cross-check (runtime-compile-events ⊆ COMPILE_MANIFEST.json) —
    # watchdog only, none of the sanitizer's numeric flags, so the
    # measured numbers are undisturbed.  Installed BEFORE jax first
    # dispatches so no compile escapes the log.
    census_wd = None
    if os.environ.get("BENCH_GATE", "0") == "1":
        from kubetpu.utils.sanitize import install_compile_watchdog
        census_wd = install_compile_watchdog()
    import jax

    # the flight recorder rides every bench cycle (its < 2% overhead is
    # part of the measured number — serving runs it too); the headline
    # mode's ring is exported as PIPELINE_TRACE.json + the
    # Perfetto-loadable PIPELINE_TRACE.perfetto.json below
    from kubetpu.utils import trace as utrace
    flight = utrace.arm_flight_recorder()
    # ...and the per-pod latency SLO tracker rides next to it: every
    # case's JSON carries the per-pod latency block (pod_e2e_p50/p90/p99
    # + per-stage shares), and the pipeline doc gains the "slo" section
    # traceview digests
    from kubetpu.utils import slo as uslo
    uslo.arm_slo_tracker()
    # ...and device-side observability (kubetpu/utils/devstats.py):
    # sampled deep-timing fences give every case MEASURED per-program
    # device_time_s (honest under depth-k overlap, unlike
    # device_wait_s), the residency ledger records what actually lives
    # in HBM, and the per-case "device" block carries the roofline join
    from kubetpu.utils import devstats as udevstats
    udevstats.arm_devstats()
    # ...and the windowed sustained-load telemetry ring
    # (kubetpu/utils/telemetry.py): per-window stage quantiles / queue
    # depths / recovery events at the default 5 s cadence across every
    # case, so the pipeline doc gains the "load" section traceview
    # digests (the sustained_load case re-arms at its own finer window)
    from kubetpu.utils import telemetry as utelemetry
    utelemetry.arm_telemetry()

    detail = {"backend": jax.default_backend(), "pending": n_pods,
              "nodes": n_nodes}
    def run_case(name, case, into=detail):
        """One extra case.  An error is recorded in the artifact — a
        failure at an experimental scale must not cost the other cases
        their numbers — and fails the run at exit (errored_cases)."""
        try:
            into[name] = case()
        except Exception as e:
            import traceback
            traceback.print_exc()
            into[name] = {"error": repr(e)}

    def enabled(flag):
        return os.environ.get(flag, "1") == "1" and mesh_shape is None

    # warm-restart SLO FIRST: this process has run no jit yet, so the
    # measurement is a true restart against the persistent XLA cache
    if enabled("BENCH_RESTART"):
        run_case("warm_restart", lambda: warm_restart_case(n_nodes=n_nodes))
    headline = None
    trace_doc = chrome_doc = None
    for mode in modes:
        if headline is None:
            # the exported trace covers exactly the headline mode's cycles
            flight.clear()
        best, first, outcomes, sched, stats = run_mode(
            mode, n_nodes, n_pods, existing_per_node, repeats,
            mesh_shape=mesh_shape)
        d, pods_per_sec = mode_summary(mode, best, first, outcomes, sched,
                                       stats)
        detail[mode] = d
        sched.close()
        if headline is None:
            headline = (mode, pods_per_sec)
            trace_doc = flight.to_pipeline_doc(
                workload=f"{mode} {n_pods} pods x {n_nodes} nodes, "
                         f"{repeats + 1} attempts (flight recorder, last "
                         f"{flight.capacity} cycles)")
            chrome_doc = flight.to_chrome_trace()

    # the headline prints BEFORE the optional extra cases: a failure at an
    # experimental scale must never cost the recorded number
    mode, pods_per_sec = headline
    baseline = 30.0  # reference hard throughput floor (scheduler_test.go:40)
    hl = detail.get(mode, {})
    headline_doc = {
        "metric": f"e2e_{mode}_throughput_{n_pods}pods_{n_nodes}nodes",
        "value": round(pods_per_sec, 1),
        "unit": "pods/s",
        "vs_baseline": round(pods_per_sec / baseline, 2),
        # per-repeat raw + min/median spread: best-of alone cannot tell a
        # regression from run-to-run variance
        "repeat_raw_s": hl.get("repeat_raw_s", []),
        "spread": hl.get("spread", {}),
    }
    print(json.dumps(headline_doc), flush=True)

    # PIPELINE_TRACE.json now comes FROM the flight recorder (the same
    # span trees /debug/flightz serves), with a Perfetto-loadable Chrome
    # trace-event twin whose ph:"X" event count equals span_total —
    # `python tools/traceview.py PIPELINE_TRACE.json` prints the flame
    # summary
    if trace_doc is not None:
        atomic_write_json("PIPELINE_TRACE.json", trace_doc)
        atomic_write_json("PIPELINE_TRACE.perfetto.json", chrome_doc)

    if (mesh_shape is not None
            and os.environ.get("BENCH_MULTICHIP_SCALE", "1") == "1"):
        # the pod-axis mesh case rides ONLY the MULTICHIP runs (the
        # mesh exists there); placements_match gates like
        # warm_restart's under BENCH_GATE
        run_case("multichip_scale",
                 lambda: multichip_scale_case(mesh_shape))

    if enabled("BENCH_CHAIN_DRAIN"):
        run_case("chain_drain", lambda: chain_drain_case(
            n_nodes, n_pods, existing_per_node))
    if enabled("BENCH_PIPELINE"):
        run_case("pipeline_depth", lambda: pipeline_depth_case(
            n_nodes, n_pods, existing_per_node))
    if enabled("BENCH_PV"):
        run_case("pv_heavy", pv_heavy_case)
    if enabled("BENCH_PREEMPT"):
        run_case("preemption", preemption_case)
    if enabled("BENCH_NODE_FLAP"):
        run_case("node_flap", node_flap_case)
    if enabled("BENCH_REPLAY"):
        run_case("replay_fidelity", replay_fidelity_case)
    if enabled("BENCH_SUSTAINED"):
        run_case("sustained_load", sustained_load_case)

    if full:
        northstar = {}

        def ipa_heavy():
            # 10k x 5k InterPodAffinity-heavy, drained in chained 4096-pod
            # cycles — the multi-cycle drain is the serving loop's real
            # shape
            best, first, outcomes, sched, stats = run_mode(
                "gang", 5120, 10240, 1, repeats=1, batch_cap=4096,
                ipa_heavy=True, pipeline=True)
            d, pods_per_sec = mode_summary("gang", best, first, outcomes,
                                           sched, stats)
            d["pods_per_sec"] = round(pods_per_sec, 1)
            sched.close()
            return d

        run_case("e2e_gang_10240x5120_ipa_heavy", ipa_heavy, into=northstar)
        run_case("rescore_stream", rescore_case, into=northstar)
        # warm-restart SLO at the north-star serving shape, 5120 nodes
        # (the 10k-pods-per-drain workload; <20 s target)
        run_case("warm_restart_5120n", lambda: warm_restart_case(
            n_nodes=5120, existing_per_node=1), into=northstar)
        # record drift-gate floors for this backend next to the northstar
        # shapes, so BENCH_GATE=1 runs can detect regressions
        northstar["gate"] = gate_entries(detail, northstar)
        detail["northstar"] = northstar
        atomic_write_json("NORTHSTAR.json", northstar)

    # the Tesserae question, answered offline from the run's own ledger:
    # project the registered per-table shape formulas to the 100k pods x
    # 10k nodes north-star and record whether it fits per v5e shard
    # (tools/devplan replays the same projection from the committed JSON)
    ds = udevstats.devstats()
    if ds is not None:
        ledger = ds.ledger()
        if ledger["entries"]:
            # the FULL ledger (per-table shapes + dim tags) rides the
            # committed artifact so tools/devplan can re-project it at
            # ANY shape offline — the projection below is just the
            # north-star instance
            detail["device_ledger"] = ledger
            detail["northstar_hbm_projection"] = udevstats.project(
                ledger, 10000, 100000, shards=8,
                groups=("delta-resident", "chain"))

    print(json.dumps({"detail": detail}), file=sys.stderr)
    # BENCH_OUT=<path>: the committed BENCH_*.json artifact, written
    # atomically so a timeout/kill mid-run can never truncate it
    out_path = os.environ.get("BENCH_OUT")
    if out_path:
        atomic_write_json(out_path,
                          {"headline": headline_doc, "detail": detail})

    # BENCH_GATE=1: fail the run (exit 3) when gang/chain_drain throughput
    # regresses beyond the floors recorded in NORTHSTAR.json — perf
    # regressions surface in CI instead of at the next re-anchor.  Runs
    # AFTER the artifacts are written so a failing run is still inspectable.
    if os.environ.get("BENCH_GATE", "0") == "1":
        failures = northstar_gate(detail)
        # census cross-check: every compile event the watchdog observed
        # for a REGISTERED kernel program must be a COMPILE_MANIFEST.json
        # row — exact at census rungs; at serving shapes, programs the
        # committed closure (CLOSURE_MANIFEST.json) proves classify by
        # closure membership (committed leaf structure + pow2-licensed
        # dims under the north-star caps), everything else by the legacy
        # structural heuristic.  An "outside" event means the observed
        # compile surface drifted from the committed census/closure.
        if census_wd is not None:
            try:
                from tools.kubecensus.manifest import (load_closure,
                                                       load_manifest,
                                                       match_compile_events)
                rows = load_manifest()
                if rows:
                    rep = match_compile_events(census_wd.counts, rows,
                                               closure=load_closure())
                    print(json.dumps({"census_check": rep}),
                          file=sys.stderr)
                    for ev in rep["outside"]:
                        failures.append("compile event outside "
                                        "COMPILE_MANIFEST.json: " + ev)
            except ImportError:
                pass   # bench run outside the repo tree
        if failures:
            print(json.dumps({"bench_gate": "FAIL",
                              "regressions": failures}), file=sys.stderr)
            sys.exit(3)
        print(json.dumps({"bench_gate": "PASS"}), file=sys.stderr)
    errored = errored_cases(detail)
    if errored:
        print(json.dumps({"bench_errors": errored}), file=sys.stderr)
        sys.exit(4)


if __name__ == "__main__":
    main()
