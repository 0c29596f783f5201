#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the serving path still starts
and schedules correctly on the chip.

One process, one pass through the entry points ``python -m kubetpu``
wires (store -> Scheduler -> SchedulerServer -> Scheduler.run()), at the
cluster size upstream scheduler_perf's 5000-node rows use
(SchedulingBasic5000Nodes: 5,000 nodes of the 110-pod / 4-CPU / 32-Gi
shape, 5,000 bound init pods).  It refuses to pass on anything but a
TPU.  Phases:

  gang        daemon + HTTP server, then a 4,096-pod backlog of the
              blended mix (1/3 soft zone spread, 1/5 hostname
              anti-affinity) at batch_size 1024; /healthz, /metrics and
              /debug/flightz answered while it serves
  warm        the same shapes drained twice, synchronously: the second
              drain must not compile anything
  sequential  the config-default mode, 256 pods on the same cluster
  sync-probe  what block_until_ready and a small readback cost here
  mesh        (>= 4 devices) the gang drain under mesh_shape (1, 4) and
              (2, 2): placements equal the single-device run, resident
              cluster sharded over four distinct devices

*Correct* is decided by a plain host re-check that shares no code with
the kernels (host_recheck below), from the store alone.  Every second
printed here is a smoke reading, not a benchmark.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", ...}}`` only when every
phase passed on a TPU; anything else exits nonzero without that line.
``--rehearse`` runs the same phases at a toy size on whatever backend
there is, to debug the script itself; it says so and never passes.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
import traceback
import urllib.request

NODES = 5000            # never cut
BACKLOG = 4096
BATCH = 1024
WARM_BACKLOG = 2048
SEQ_PODS = 256
MESH_BACKLOG = 2048     # cut from 4,096: three drains share one 4-chip call
BIG_BATCH = 8192


# --------------------------------------------------------------- host check
#
# Plain Python over the store's objects.  Nothing here imports kubetpu: the
# quantities are parsed here, the sums are Python sums.

_SUFFIX = {"Ki": 2 ** 10, "Mi": 2 ** 20, "Gi": 2 ** 30, "Ti": 2 ** 40,
           "k": 1e3, "M": 1e6, "G": 1e9, "T": 1e12}


def parse_quantity(q) -> float:
    """A Kubernetes resource quantity in base units (cores, bytes)."""
    s = str(q)
    if s.endswith("m"):
        return float(s[:-1]) / 1000.0
    for suffix, mult in _SUFFIX.items():
        if s.endswith(suffix):
            return float(s[:-len(suffix)]) * mult
    return float(s)


def pod_requests(pod):
    cpu = mem = 0.0
    for c in pod.spec.containers:
        req = c.resources.requests or {}
        cpu += parse_quantity(req.get("cpu", 0))
        mem += parse_quantity(req.get("memory", 0))
    return cpu, mem


def _selects(selector, pod) -> bool:
    labels = pod.metadata.labels or {}
    return all(labels.get(k) == v
               for k, v in (selector.match_labels or {}).items())


def _hostname_anti_terms(pod):
    aff = pod.spec.affinity
    anti = aff.pod_anti_affinity if aff is not None else None
    terms = (anti.required_during_scheduling_ignored_during_execution
             if anti is not None else [])
    return [t for t in terms if t.topology_key == "kubernetes.io/hostname"]


def host_recheck(nodes, pods, binds):
    """Violations of what a correct placement must satisfy, as strings.

    nodes/pods: the store's objects.  binds: every (pod key, node name)
    bind the store announced, in order.  Checked: every bound pod sits on
    exactly one existing node; no pod was bound twice; per-node summed
    requests stay within allocatable (cpu, memory, pod count); no pod
    with a required hostname anti-affinity term shares its node with a
    pod that term selects."""
    out = []
    node_names = {n.metadata.name for n in nodes}
    bound_to = collections.defaultdict(list)
    for key, node in binds:
        bound_to[key].append(node)
    for key, where in bound_to.items():
        if len(where) > 1:
            out.append(f"pod {key} bound {len(where)} times: {where}")
    listed = collections.Counter(
        f"{p.metadata.namespace}/{p.metadata.name}" for p in pods)
    for key, n in listed.items():
        if n > 1:
            out.append(f"pod {key} listed {n} times")
    on_node = collections.defaultdict(list)
    for p in pods:
        if p.spec.node_name:
            if p.spec.node_name not in node_names:
                out.append(f"pod {p.metadata.name} bound to unknown node "
                           f"{p.spec.node_name}")
            on_node[p.spec.node_name].append(p)
    for n in nodes:
        here = on_node.get(n.metadata.name, [])
        alloc = n.status.allocatable
        requests = [pod_requests(p) for p in here]
        cpu = sum(r[0] for r in requests)
        mem = sum(r[1] for r in requests)
        for what, used, cap in (
                ("cpu", cpu, parse_quantity(alloc["cpu"])),
                ("memory", mem, parse_quantity(alloc["memory"])),
                ("pods", len(here), parse_quantity(alloc["pods"]))):
            if used > cap * (1 + 1e-9):
                out.append(f"node {n.metadata.name} over-committed on "
                           f"{what}: {used:g} > {cap:g}")
        for p in here:
            for term in _hostname_anti_terms(p):
                for other in here:
                    if (other is not p
                            and other.metadata.namespace
                            == p.metadata.namespace
                            and _selects(term.label_selector, other)):
                        out.append(
                            f"anti-affinity pair on {n.metadata.name}: "
                            f"{p.metadata.name} / {other.metadata.name}")
    return out


def predicted_unbound(nodes, bound_before, pending) -> int:
    """How many pending pods cannot be placed, from counting alone: a pod
    with a required hostname anti-affinity term needs a node that holds
    no pod the term selects, and two pods of one such group cannot share
    a node.  Capacity is not modelled — the caller's cluster has room to
    spare, and host_recheck would flag a capacity error anyway."""
    groups = collections.defaultdict(list)
    for p in pending:
        for term in _hostname_anti_terms(p):
            sel = tuple(sorted((term.label_selector.match_labels
                                or {}).items()))
            groups[(p.metadata.namespace, sel)].append(p)
    short = 0
    for (ns, sel), members in groups.items():
        taken = {b.spec.node_name for b in bound_before
                 if b.metadata.namespace == ns
                 and all((b.metadata.labels or {}).get(k) == v
                         for k, v in sel)}
        short += max(0, len(members) - (len(nodes) - len(taken)))
    return short


# ------------------------------------------------------------------ helpers


class Failed(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Failed(msg)


class Sizes:
    def __init__(self, rehearse: bool):
        self.rehearse = rehearse
        self.nodes = 96 if rehearse else NODES
        self.backlog = 192 if rehearse else BACKLOG
        self.batch = 64 if rehearse else BATCH
        self.warm = 128 if rehearse else WARM_BACKLOG
        self.seq = 32 if rehearse else SEQ_PODS
        self.mesh = 128 if rehearse else MESH_BACKLOG


def watch_binds(store):
    binds = []

    def on_pod(event, old, new):
        if (event == "update" and new.spec.node_name
                and old.spec.node_name != new.spec.node_name):
            binds.append((f"{new.metadata.namespace}/{new.metadata.name}",
                          new.spec.node_name))
    store.subscribe("Pod", on_pod)
    return binds


def make_sched(store, mode, batch, seed, async_binding=True, **cfg_kw):
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils.metrics import SchedulerMetrics
    cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                     mode=mode, batch_size=batch, **cfg_kw)
    return Scheduler(store, config=cfg, metrics=SchedulerMetrics(),
                     seed=seed, async_binding=async_binding)


def http_get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=30) as r:
        return r.status, r.read().decode()


def wait_drained(sched, store, names, timeout):
    """Until every named pod is bound or parked unschedulable with the
    queue otherwise idle.  Returns {pod name: node or ""}."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        q = sched.queue
        unbound = [n for n in names
                   if not store.get_pod("default", n).spec.node_name]
        if (len(unbound) == len(q.unschedulable_q)
                and not len(q.active_q) and not len(q.backoff_q)):
            break
        time.sleep(0.25)
    else:
        raise Failed(f"drain timed out after {timeout}s")
    sched.wait_for_inflight_binds()
    return {n: store.get_pod("default", n).spec.node_name for n in names}


# -------------------------------------------------------------------- world


def build_world(n_nodes, n_pods, existing_per_node):
    """The smoke's cluster and backlog: zoned hollow nodes with
    ``existing_per_node`` bound pods each, and ``n_pods`` pending pods of
    scheduler_perf's blended mix (1/3 soft zone spread, 1/5 hostname
    anti-affinity on the app group)."""
    from kubetpu.harness import hollow
    return (hollow.restart_world(n_nodes, existing_per_node),
            hollow.restart_wave(n_pods, prefix="pend-"))


def explain(sched, pods):
    """Attribute every pod of ``pods`` (the unbound ones) to its blocking
    filter(s) against the final cluster state (the state in which the
    last failures occurred)."""
    import jax
    import numpy as np

    from kubetpu.api import types as api
    from kubetpu.framework.types import PodInfo
    from kubetpu.models import programs
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder

    sched.cache.update_snapshot(sched.snapshot)
    sb = SnapshotBuilder()
    pinfos = [PodInfo(p) for p in pods]
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
    blocking = np.asarray(blocking)[:, :len(pods)]
    counts = {name: int(blocking[i].sum())
              for i, name in enumerate(cfg.filters) if blocking[i].any()}
    counts["_unschedulable"] = int(np.asarray(no_feas)[:len(pods)].sum())
    return counts


def verify_store(sched, store, before, pending, binds):
    """host_recheck + attribution of every unbound pod, from the store."""
    pods = store.list("Pod")
    nodes = store.list("Node")
    bad = host_recheck(nodes, pods, binds)
    check(not bad, f"host re-check: {len(bad)} violation(s), first: "
                   f"{bad[:3]}")
    unbound = [p for p in pending
               if not store.get_pod("default", p.metadata.name)
               .spec.node_name]
    want = predicted_unbound(nodes, before, pending)
    check(len(unbound) == want,
          f"{len(unbound)} pods unbound, host count predicts {want}")
    attributed = {}
    if unbound:
        attributed = explain(sched, unbound)
        check(attributed.get("_unschedulable") == len(unbound),
              f"unattributed unbound pods: {attributed} vs {len(unbound)}")
    return len(pending) - len(unbound), attributed


def clean_run(sched, what):
    check(not sched.recovery_log,
          f"{what}: recovery_log not empty: {list(sched.recovery_log)[:3]}")


# -------------------------------------------------------------------- phases


def daemon_drain(sz, mode, n_pods, batch, seed, http=False):
    """The serving path the way ``python -m kubetpu`` wires it: store ->
    Scheduler -> SchedulerServer -> Scheduler.run() (prewarm included),
    then the backlog arrives."""
    from kubetpu.server import SchedulerServer
    store, pending = build_world(sz.nodes, n_pods, 1)
    before = [p for p in store.list("Pod") if p.spec.node_name]
    binds = watch_binds(store)
    sched = make_sched(store, mode, batch, seed)
    server = SchedulerServer(sched, port=0)
    port = server.start()
    info = {"nodes": sz.nodes, "init_pods": len(before),
            "backlog": n_pods, "batch": batch}
    try:
        t0 = time.time()
        sched.run()
        info["prewarm_smoke_s"] = round(time.time() - t0, 2)
        clean_run(sched, "prewarm")
        t0 = time.time()
        for p in pending:
            store.add(p)
        names = [p.metadata.name for p in pending]
        placements = wait_drained(sched, store, names, timeout=900)
        info["drain_smoke_s"] = round(time.time() - t0, 2)
        info["cycles"] = sched.cycle_count
        info["delta_cycles"] = sched.delta_cycle_count
        info["resyncs"] = sched.resync_count
        if http:
            info["http"] = http_checks(port, store, names)
    finally:
        sched.close()
        server.stop()
    clean_run(sched, mode)
    info["bound"], info["unbound_attribution"] = verify_store(
        sched, store, before, pending, binds)
    return info, placements


def http_checks(port, store, names):
    status, body = http_get(port, "/healthz")
    check(status == 200 and body.strip() == "ok", f"/healthz: {status}")
    status, body = http_get(port, "/metrics")
    check(status == 200, f"/metrics: {status}")
    scheduled = None
    for line in body.splitlines():
        if (line.startswith("scheduler_schedule_attempts_total")
                and 'result="scheduled"' in line):
            scheduled = int(float(line.rsplit(" ", 1)[1]))
    bound = sum(1 for n in names
                if store.get_pod("default", n).spec.node_name)
    check(scheduled == bound,
          f"/metrics scheduled={scheduled}, store has {bound} bound")
    status, body = http_get(port, "/debug/flightz")
    check(status == 200 and "armed" in json.loads(body),
          f"/debug/flightz: {status}")
    return {"healthz": "ok", "metrics_scheduled": scheduled}


def shard_devices(sched):
    """Distinct devices holding shards of the resident cluster's node
    tables — all of the mesh's, not all on the first."""
    resident = next(iter(sched._delta.values())).cluster
    return sorted({s.device.id
                   for s in resident.allocatable.addressable_shards})


def sync_drain(store, pending, sched, what):
    """Synchronous drain: the caller's thread runs every
    cycle, so the batches — and the shapes — are the same in every run.
    Returns ({pod name: node or ""}, pods bound), host-rechecked."""
    before = [p for p in store.list("Pod") if p.spec.node_name]
    binds = watch_binds(store)
    for p in pending:
        store.add(p)
    placements = {}
    try:
        while True:
            out = sched.schedule_pending(timeout=0.2)
            if not out:
                break
            for o in out:
                placements[o.pod.metadata.name] = o.node
    finally:
        sched.close()
    clean_run(sched, what)
    bound, _ = verify_store(sched, store, before, pending, binds)
    return placements, bound


def phase_gang(sz, seed):
    info, placements = daemon_drain(sz, "gang", sz.backlog, sz.batch, seed,
                                    http=True)
    check(info["cycles"] >= 4, f"only {info['cycles']} cycles")
    return info, placements


def phase_warm(sz, seed, watchdog):
    """Two identical synchronous drains; the second must find every
    program in the process's jit caches."""
    info = {"nodes": sz.nodes, "backlog": sz.warm, "batch": sz.batch}
    results = []
    for label in ("first", "second"):
        c0 = watchdog.compile_count()
        store, pending = build_world(sz.nodes, sz.warm, 1)
        sched = make_sched(store, "gang", sz.batch, seed,
                           async_binding=False)
        placed, info[f"{label}_bound"] = sync_drain(store, pending, sched,
                                                    f"warm {label}")
        results.append(placed)
        info[f"{label}_compiles"] = watchdog.compile_count() - c0
    check(results[0] == results[1], "two identical drains placed differently")
    check(info["second_compiles"] == 0,
          f"warm pass compiled {info['second_compiles']} program(s)")
    return info


def phase_sequential(sz, seed):
    info, _ = daemon_drain(sz, "sequential", sz.seq, min(sz.seq, 256), seed)
    return info


def phase_sync_probe(sz, seed):
    """Does block_until_ready on the packed result return only after the
    program is done, and what does the small readback cost?"""
    import jax
    import numpy as np
    from kubetpu.api import types as api
    from kubetpu.framework.types import NodeInfo, PodInfo
    from kubetpu.harness import hollow
    from kubetpu.models import programs
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.models.gang import run_auction
    from kubetpu.state.tensors import SnapshotBuilder
    infos = [NodeInfo(n) for n in hollow.make_nodes(sz.nodes, zones=8)]
    pinfos = [PodInfo(p) for p in hollow.make_pods(sz.batch, group_labels=0)]
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=programs.DEFAULT_FILTER_PLUGINS,
        scores=programs.DEFAULT_SCORE_PLUGINS,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0))

    def auction(i):
        return run_auction(cluster, batch, cfg, jax.random.PRNGKey(i),
                           intra_batch_topology=False)
    np.asarray(auction(0).packed)              # compile + warm
    reads = collections.defaultdict(list)
    for i in range(1, 6):
        t0 = time.perf_counter()
        res = auction(i)
        t1 = time.perf_counter()
        res.packed.block_until_ready()
        t2 = time.perf_counter()
        np.asarray(res.packed)
        t3 = time.perf_counter()
        res = auction(i + 10)
        t4 = time.perf_counter()
        np.asarray(res.packed)
        t5 = time.perf_counter()
        reads["dispatch_s"].append(t1 - t0)
        reads["block_until_ready_s"].append(t2 - t1)
        reads["readback_after_block_s"].append(t3 - t2)
        reads["readback_without_block_s"].append(t5 - t4)
    return {k: sorted(v)[len(v) // 2] for k, v in reads.items()} | {
        "readings": 5, "program": f"run_auction {sz.batch}x{sz.nodes}",
        "packed_bytes": int(res.packed.nbytes)}


def phase_mesh(sz, seed):
    """The gang drain through Scheduler under mesh_shape — the shard_map
    programs — against the same drain on one device.  Synchronous drains:
    the batches must be identical for the placements to be comparable."""
    import jax
    n = jax.device_count()
    if n < 4:
        return f"not run: {n} device(s)"
    info = {"nodes": sz.nodes, "backlog": sz.mesh, "batch": sz.batch}
    ref = None
    for shape in (None, (1, 4), (2, 2)):
        label = "single_device" if shape is None else "%dx%d" % shape
        store, pending = build_world(sz.nodes, sz.mesh, 1)
        sched = make_sched(store, "gang", sz.batch, seed,
                           async_binding=False, mesh_shape=shape)
        t0 = time.time()
        placed, bound = sync_drain(store, pending, sched, f"mesh {label}")
        info[label] = {"bound": bound, "cycles": sched.cycle_count,
                       "smoke_s": round(time.time() - t0, 2)}
        if shape is None:
            ref = placed
            continue
        devices = shard_devices(sched)
        info[label]["shard_devices"] = devices
        check(len(devices) == 4, f"mesh {label}: shards on {devices}")
        diff = [k for k in ref if ref[k] != placed[k]]
        check(not diff, f"mesh {label}: {len(diff)} placements differ from "
                        f"the single-device run, e.g. {diff[:3]}")
    return info


def phase_big_batch(sz, seed):
    """One 8,192-pod gang cycle on the 5,000-node cluster (not gating:
    the pre-PR-1 notes say it died with a device error)."""
    store, pending = build_world(sz.nodes, BIG_BATCH, 1)
    sched = make_sched(store, "gang", BIG_BATCH, seed, async_binding=False)
    t0 = time.time()
    _, bound = sync_drain(store, pending, sched, "big batch")
    return {"pods": BIG_BATCH, "cycles": sched.cycle_count, "bound": bound,
            "smoke_s": round(time.time() - t0, 2)}


# ---------------------------------------------------------------------- main


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="scheduler tie-break seed (the worlds themselves "
                         "are deterministic)")
    ap.add_argument("--phases", default="gang,warm,sequential,"
                    "sync-probe,mesh",
                    help="comma-separated subset; a pass needs the default")
    ap.add_argument("--big-batch", action="store_true",
                    help=f"also try one {BIG_BATCH}-pod cycle (not gating)")
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; never passes")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    import jaxlib
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    print(f"platform: {device['platform']}  device_kind: {device['kind']}  "
          f"devices: {device['count']}  jax {jax.__version__}  "
          f"jaxlib {jaxlib.__version__}  libtpu {libtpu_version}",
          flush=True)
    if device["platform"] != "tpu" and not args.rehearse:
        print("chip_smoke: no TPU — refusing to run", file=sys.stderr)
        return 2

    from kubetpu.utils.compilation import enable_persistent_cache
    from kubetpu.utils.sanitize import (install_compile_timer,
                                        install_compile_watchdog)
    cache_dir = enable_persistent_cache()
    print(f"compile cache: {cache_dir}  "
          f"(JAX_COMPILATION_CACHE_DIR="
          f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')})", flush=True)
    timer = install_compile_timer()
    watchdog = install_compile_watchdog()

    sz = Sizes(args.rehearse)
    phases = {"gang": lambda: phase_gang(sz, args.seed)[0],
              "warm": lambda: phase_warm(sz, args.seed, watchdog),
              "sequential": lambda: phase_sequential(sz, args.seed),
              "sync-probe": lambda: phase_sync_probe(sz, args.seed),
              "mesh": lambda: phase_mesh(sz, args.seed)}
    wanted = args.phases.split(",")
    if args.big_batch:
        phases["big-batch"] = lambda: phase_big_batch(sz, args.seed)
        wanted.append("big-batch")
    report = {"device": device, "cache_dir": cache_dir, "seed": args.seed,
              "rehearsal": args.rehearse, "phases": {}}
    failed = []
    t_start = time.time()
    for name in wanted:
        snap = timer.snapshot()
        t0 = time.time()
        try:
            result = phases[name]()
            status = ("passed" if not isinstance(result, str) else result)
        except Exception as e:
            traceback.print_exc()
            result, status = {"error": repr(e)}, "FAILED"
            if name != "big-batch":
                failed.append(name)
        entry = {"status": status, "smoke_s": round(time.time() - t0, 2),
                 "compile": timer.delta(snap, timer.snapshot())}
        if isinstance(result, dict):
            entry.update(result)
        report["phases"][name] = entry
        print(f"phase {name}: {status}  {json.dumps(entry)}", flush=True)
    report["compile_total"] = timer.snapshot()
    report["smoke_total_s"] = round(time.time() - t_start, 2)
    print(f"compile (smoke): {json.dumps(report['compile_total'])}  "
          f"total {report['smoke_total_s']} s", flush=True)

    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=1, default=str)

    complete = set(ap.get_default("phases").split(",")) <= set(wanted)
    if failed or args.rehearse or not complete:
        print(f"chip_smoke: NOT a pass (failed={failed}, "
              f"rehearsal={args.rehearse}, all phases={complete})",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
