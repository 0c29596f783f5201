"""Scheduler core: the serving loop tying queue, cache, framework and the
device programs together.

reference: pkg/scheduler/scheduler.go (Scheduler :69, New :210, Run :339,
scheduleOne :509, assume :435, bind :457, recordSchedulingFailure :391,
skipPodSchedule :391) and pkg/scheduler/eventhandlers.go (addAllEventHandlers
:362).  The reference schedules one pod per cycle; this scheduler pops a
BATCH from the queue and runs the whole batch through one jitted
sequential-replay program (kubetpu/models/sequential.py), preserving the
serial semantics (pod i sees placements 0..i-1) while amortizing all host
work — the design lever named in SURVEY.md §7 step 2.

Cycle pipeline (mirroring scheduleOne's phases):
  pop batch -> snapshot (incremental) -> tensorize -> PreFilter(host) +
  host filter masks -> DEVICE filter+score+select (scan) ->
  per pod: Reserve -> assume -> Permit -> async bind cycle
  (WaitOnPermit -> PreBind -> Bind -> FinishBinding -> PostBind)
with failures flowing through Unreserve -> ForgetPod ->
recordSchedulingFailure exactly like the reference (scheduler.go:586-687).
"""

from __future__ import annotations

import itertools
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from .api import types as api
from .apis.config import (KubeSchedulerConfiguration, KubeSchedulerProfile)
from .bindlane import BindFold, BindJob, BindLane
from .client.store import ClusterStore
from .framework import interface as fw
from .framework.interface import Code, CycleState, Status
from .framework.runtime import Framework
from .framework.types import (NodeInfo, PodClasses, PodInfo, QueuedPodInfo,
                              class_pod_infos, classify_pods)
from .models import programs
from .models.batch import (PodBatchBuilder, batch_score_sets,
                           live_term_sets, score_rows_spliced)
from .models.sequential import schedule_sequential
from .plugins.intree import new_in_tree_registry
from .schedqueue.queue import SchedulingQueue
from .state.cache import SchedulerCache, Snapshot
from .state.delta import DeltaTensorizer
from .state.tensors import SnapshotBuilder
from .utils import chaos as uchaos
from .utils import heap as uheap
from .utils import journal as ujournal
from .utils import trace as utrace
from .utils.decisions import DecisionLog, PodDecision
from .utils.trace import Trace


def _lap(acc: List[float], step: int) -> None:
    """The commit loop's running split (_commit_group): charge the time
    since the last stamp, ``acc[-1]``, to ``acc[step]`` and stamp anew."""
    now = time.perf_counter()
    acc[step] += now - acc[-1]
    acc[-1] = now


def _assumed(pod: api.Pod, node_name: str) -> api.Pod:
    """The pod as the cache assumes it: a new object with the pod's
    fields and a new spec with the spec's, ``node_name`` set -- field for
    field what ``copy.copy`` of the two gives, without its reduce
    protocol.  A shallow clone is enough: the cache reads spec,
    containers and labels, which the scheduler never mutates."""
    assumed = object.__new__(type(pod))
    assumed.__dict__ = pod.__dict__.copy()
    spec = object.__new__(type(pod.spec))
    spec.__dict__ = pod.spec.__dict__.copy()
    spec.node_name = node_name
    assumed.spec = spec
    return assumed


class _LadderOwed(NamedTuple):
    """A binding cycle stopped where its retry ladder owes a sleep
    (Scheduler._bind_cycle_inner): what the thread that resumes it needs."""
    status: Status          # the rejected first Bind
    bind_start: float


def _vocab_caps(table):
    """Tensor-width signature chained cycles compare to detect overflow
    (tensor shapes would change) — ONE definition shared with the
    DeltaTensorizer's resync guard, see state/tensors.vocab_signature."""
    from .state.tensors import vocab_signature
    return vocab_signature(table)


@dataclass
class ScheduleOutcome:
    pod: api.Pod
    node: str = ""                 # "" => unschedulable
    err: Optional[str] = None
    n_feasible: int = 0
    preemption_may_help: bool = True


@dataclass
class PreparedCycle:
    """Host-side state of one scheduling cycle between tensorize and
    commit — the unit the pipelined drain keeps in flight."""
    fwk: "Framework"
    trace: Trace
    chain_seq0: int
    node_infos: list
    states: Dict[str, CycleState]
    live: list
    pinfos: list
    builder: SnapshotBuilder
    cluster: object
    batch: object
    host_relevant: Dict[str, bool]
    host_ok_dev: object
    cfg: programs.ProgramConfig
    cycle_ctx: object
    # classify_pods of ``live``'s pods, as prepare grouped them: what the
    # commit loop asks a class and not a pod (Framework.commits_bare)
    classes: PodClasses
    needs_topo: bool = True
    used_chain: bool = False
    chain_pod_uids: list = field(default_factory=list)
    score_bias: object = None   # [B, N] weighted host Score plugin totals
    # per-pod host-filter rejection reasons (uid -> reason -> node count),
    # folded into the DecisionLog by the commit-path audit
    host_reject: Dict[str, Dict[str, int]] = field(default_factory=dict)
    # the cycle's host-plugin relevance map (_host_relevance) — kept so a
    # scatter recovery's re-prepare never re-walks the plugin predicates
    relevance: Optional[Dict[str, Tuple[bool, bool]]] = None
    # wall-clock of the device dispatch start — the deadline guard
    # measures dispatch-to-readback against it (0.0 = never dispatched)
    dispatch_t0: float = 0.0
    # CompileTimer snapshot taken at dispatch_t0 (deadline armed only):
    # a cycle with any compile/cache-load activity is exempt from the
    # deadline, so a first-compile of a new pod bucket — legitimate,
    # bounded work — can never trip it and demote a healthy backend
    compile_snap: Optional[dict] = None
    # host-side seconds spent inside this cycle's dispatch->readback
    # window on OTHER work (the pipelined drain runs k-1's commit loop
    # there) — subtracted before the deadline comparison
    host_exempt_s: float = 0.0
    # wall-clock when this cycle was parked in the pipeline's in-flight
    # ring: caller think time between schedule_pending calls is host
    # time too, and must not count against the dispatch deadline (a
    # device hang still counts — it blocks the READBACK, which runs
    # after pickup)
    parked_t: float = 0.0
    # cycle-journal capture (utils/journal.py, armed only): the cycle's
    # cluster-input provenance — ("resync"|"delta"|"noop", payload) from
    # the DeltaTensorizer seam or ("chain", pads) for chained cycles —
    # plus the RNG fold counter and sequential start index the dispatch
    # consumed, and the pipeline ring slot the cycle parked in
    journal_input: Optional[tuple] = None
    journal_rng: int = 0
    journal_start: int = 0
    ring_slot: int = 0
    # DOUBLE-BUFFERED batch transfer (mesh serving): the sharded device
    # copy of `batch`, upload STARTED at prepare time so the host->device
    # transfer of wave k+1 overlaps wave k's auction on the device
    # (device_put is async: it returns once the transfer is issued).
    # _dispatch_group consumes it instead of re-uploading; None on
    # single-chip profiles
    batch_dev: object = None


class Scheduler:
    """reference: scheduler.go:69."""

    def __init__(self, store: ClusterStore,
                 config: Optional[KubeSchedulerConfiguration] = None,
                 registry=None, seed: int = 0, async_binding: bool = True,
                 metrics=None, recorder=None):
        # warm restarts must not recompile byte-identical programs — the
        # persistent cache is a serving default, not a bench trick
        from .utils.compilation import enable_persistent_cache
        enable_persistent_cache()
        # KUBETPU_AOT_DIR: arm the serialized-executable runtime so prewarm
        # can deserialize build-time artifacts instead of tracing (falls
        # back silently on env mismatch — the trace path always works)
        from .utils import aot as _aot
        _aot.maybe_arm_from_env()
        # KUBETPU_CHAOS: arm the fault-injection registry (utils/chaos.py);
        # disarmed (the default) every injection site is one attribute read
        uchaos.maybe_arm_from_env()
        # KUBETPU_JOURNAL=<dir>: arm the durable cycle journal
        # (utils/journal.py) — every committed cycle appends one
        # self-contained replayable record; disarmed, every seam is one
        # attribute read (tests/test_journal.py poison test)
        ujournal.maybe_arm_from_env()
        import jax
        self.store = store
        self.config = config or KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()])
        if not self.config.profiles:
            self.config.profiles = [KubeSchedulerProfile()]
        self.metrics = metrics
        if recorder is None:
            # reference: profile/profile.go:33 NewRecorderFactory — every
            # profile gets a real recorder; the store plays the event sink
            from .utils.events import EventBroadcaster
            self.broadcaster = EventBroadcaster(sink=store)
            recorder = self.broadcaster.new_recorder()
        self.recorder = recorder or None
        self.cache = SchedulerCache(
            expire_listener=lambda pod: self._mark_chain_dirty())
        registry = registry or new_in_tree_registry()
        # plugin-EXISTENCE validation happens HERE, against the MERGED
        # registry (out-of-tree plugins included) — the reference rejects
        # unknown plugins at framework build time (framework.go:205);
        # config load validates everything else
        from .apis.load import validate as validate_config
        validate_config(self.config, registry_names=set(registry))

        # one framework per profile (reference: profile/profile.go:59 Map)
        self.profiles: Dict[str, Framework] = {}
        for prof in self.config.profiles:
            self.profiles[prof.scheduler_name] = Framework(
                registry, prof, client=store, metrics=metrics)

        from .extender import HTTPExtender
        self.extenders = [HTTPExtender(e) for e in self.config.extenders]

        any_fw = next(iter(self.profiles.values()))
        self.queue = SchedulingQueue(
            sort_key=any_fw.queue_sort_key,
            pod_initial_backoff=self.config.pod_initial_backoff_seconds,
            pod_max_backoff=self.config.pod_max_backoff_seconds,
            metrics=metrics)
        self.snapshot = Snapshot()
        self._rng_counter = seed
        # rotating node-search start (reference: nextStartNodeIndex,
        # generic_scheduler.go:451); persists across cycles
        self._next_start_node_index = 0
        # cycle chaining (SURVEY §7 delta updates): in gang mode the
        # auction's materialized cluster IS the next cycle's snapshot
        # tensors, so successive drain cycles skip the full re-tensorize.
        # Any store event the chain does not account for (node changes,
        # external binds, deletions) marks it dirty -> full rebuild.
        # written by bind threads (_forget) racing the serving thread
        self._chain = None  # dict(builder, cluster, pod_uids, caps)  # kubelint: guarded-by(_chain_lock)
        # monotonic event sequence: handlers bump it AFTER mutating the
        # cache.  The scheduler captures the sequence BEFORE snapshotting,
        # so "bump visible in the capture" implies "mutation visible to the
        # snapshot"; a mutation whose bump lands after the capture makes
        # the chain's stored sequence stale at its next use — the race can
        # only over-invalidate, never miss an event
        self._chain_seq = 0
        self._chain_lock = threading.Lock()
        # device mesh for the serving path: mesh_shape=(pods, nodes) runs
        # every cycle's program through parallel/mesh.py sharding (the
        # reference's 16-goroutine parallelizer runs on every cycle,
        # internal/parallelize/parallelism.go:26-43); None = single device
        self._mesh = None
        if self.config.mesh_shape:
            from .parallel import mesh as pmesh
            self._mesh = pmesh.make_mesh(tuple(self.config.mesh_shape))
        self._jax = jax
        # cumulative wall time spent blocked on the per-cycle packed
        # readback — the cycle's ONE device->host sync, so the one place
        # the serving thread waits for the device; benchmarks read this
        # for the host/device split
        self.device_wait_s = 0.0
        # committed scheduling cycles (benchmark/diagnostics surface — the
        # perf harness reports it next to device_wait_s)
        self.cycle_count = 0
        # auction round count of the most recent gang cycle (diagnostics)
        self.last_gang_rounds = 0
        self._async_binding = async_binding
        # per-pod decision audit (utils/decisions.py): bounded, on by
        # default, disabled with KUBETPU_AUDIT=0 — disabled, no commit
        # path takes its lock
        self.decisions = DecisionLog()
        # flight-recorder drop count already folded into the metrics
        # counter (serving thread only)
        self._flight_dropped_seen = 0
        # (failed-uid set, audit rows) of the last decision audit — the
        # retry-churn dedup in _commit_group (serving thread only)
        self._audit_cache = None
        # incremental tensorization (state/delta.py): one device-resident
        # cluster per profile, updated by bounded scatters; the full
        # rebuild is demoted to its anti-entropy resync (serving thread
        # only, like _audit_cache)
        self._delta: Dict[str, DeltaTensorizer] = {}
        # prepared-but-not-yet-dispatched cycles whose double-buffered
        # batch upload is in flight (mesh serving): their dispatch will
        # still READ the resident cluster, so the delta scatter's
        # donation is withheld while any of them exists —
        # DeltaTensorizer.safe_to_donate stays the single gate, this
        # list just joins the in-flight ring in feeding it.  Serving
        # thread only (appended in _prepare_group, removed at dispatch
        # or discard)
        self._undispatched: List[PreparedCycle] = []
        # delta counts for harness/perf.py: updated-row counts of recent
        # delta cycles (bounded ring) + monotonic tallies so windowed
        # readers survive ring eviction (serving thread only)
        from collections import deque
        self.delta_rows = deque(maxlen=4096)
        self.delta_cycle_count = 0
        self.resync_count = 0
        # self-healing runtime: the dispatch deadline (0 = off; env
        # overrides config so an operator can arm it on a live fleet),
        # the recovery audit trail (serving thread only, like
        # _audit_cache), and the chaos fire counts already folded into
        # scheduler_faults_injected_total
        import os as _os
        _dl = _os.environ.get("KUBETPU_DISPATCH_DEADLINE")
        self._dispatch_deadline = (
            float(_dl) if _dl
            else float(getattr(self.config, "dispatch_deadline_seconds",
                               0.0) or 0.0))
        # bounded like delta_rows: a persistent fault must not grow a
        # serving daemon's memory one incident dict per cycle forever;
        # recoveries_total keeps counting past the bound
        self.recovery_log: deque = deque(maxlen=256)
        self.recoveries_total = 0
        self._chaos_seen: Dict[str, int] = {}
        # journal counters already folded into the scheduler_journal_*
        # metrics (serving thread only, like _chaos_seen)
        self._journal_seen = (0, 0)   # (records_total, dropped_total)
        # PROFILES whose discarded pipelined cycle consumed a
        # delta/resync journal capture that will never be journaled
        # (chain-break re-prepare, scatter recovery): that profile's
        # resident has advanced past what the journal stream describes,
        # so its next journaled cycle must re-anchor from the mirror or
        # replay silently diverges.  Per-profile (each profile owns its
        # own DeltaTensorizer lineage — another profile's cycle must not
        # consume the flag); serving thread only
        self._journal_force_anchor: set = set()
        # deadline grace: cycles exempt from the deadline right after a
        # recovery — the recovery itself invalidates residents and can
        # change the traced program (demotion, new pod bucket), so the
        # next dispatch legitimately pays resync/compile cost; without
        # the grace a recovery could trip the deadline it just served
        # and requeue forever (serving thread only)
        self._deadline_grace = 0
        # pipelined drain (kubetpu/pipeline.py): the depth-k executor
        # owning the bounded ring of dispatched-but-uncommitted cycles.
        # Depth 1 = synchronous, 2 = the historical double-buffered
        # chain (the default), k parks up to k-1 cycles between calls.
        # Env override so an operator can re-depth a live fleet.
        from .pipeline import PipelinedExecutor, depth_from_env
        self._pipeline = PipelinedExecutor(
            self, depth_from_env(
                getattr(self.config, "pipeline_depth", 2) or 2))
        # last committed cycle's commit-failure flag (serving thread
        # only): a failed commit invalidates the speculative chain and
        # every in-flight cycle dispatched against it
        self._last_commit_failed = False
        # (pod-axis bucket, compile-or-load seconds) per prewarmed program
        self.prewarm_report: List[Tuple[int, float]] = []
        # binding (reference: scheduler.go:628): the commit loop hands a
        # cycle's binds over as ONE job to ONE binder lane, which applies
        # them in batch order; only a bind that would BLOCK the lane (a
        # Permit wait, an extender's or a remote client's HTTP bind, a
        # retry ladder's sleep, an armed chaos bind fault: _commit,
        # _bind_cycle) goes to the pool, where it holds up nothing else
        self._bind_lane = BindLane(self._run_bind_job)
        self._bind_pool = ThreadPoolExecutor(max_workers=16,
                                             thread_name_prefix="binder")
        # hand-overs not yet known to be applied, pruned once a hand-over
        self._bind_jobs_lock = threading.Lock()
        self._bind_jobs: List[BindJob] = []  # kubelint: guarded-by(_bind_jobs_lock)
        self._stop = threading.Event()
        self._serve_thread: Optional[threading.Thread] = None
        # the collector policy (utils/heap.py): run() starts it, close()
        # stops it; a Scheduler that is never run() has none
        self._heap: Optional[uheap.HeapPolicy] = None
        # the longest pass of the serving loop so far (seconds): what
        # close() takes a cycle in flight to need (see close)
        self._longest_pass_s = 0.0
        self._closed = False
        self._add_all_event_handlers()
        # reference: scheduler.go:548 — preemption runs unless disabled
        # (DisablePreemption componentconfig field)
        if getattr(self.config, "disable_preemption", False):
            self.preemptor = None
        else:
            from .preemption import Preemptor
            self.preemptor = Preemptor(self)
        # preemption is served through the PostFilter extension point
        # (DefaultPreemption); the Preemptor instance is late-bound because
        # it needs the scheduler itself
        from .plugins.intree import DefaultPreemption
        for fwk in self.profiles.values():
            for p in fwk.post_filter_plugins:
                if isinstance(p, DefaultPreemption):
                    p.preemptor = self.preemptor

    # ------------------------------------------------------------------ events

    def _add_all_event_handlers(self) -> None:
        """reference: eventhandlers.go:362 addAllEventHandlers."""
        s = self.store

        def on_pod(event: str, old, new) -> None:
            pod = new if new is not None else old
            if event == "add":
                if pod.spec.node_name:
                    self._add_pod_to_cache(pod)
                    self._mark_chain_dirty()   # external bound add
                elif self._responsible(pod):
                    self.queue.add(pod)
            elif event == "update":
                was_assigned = bool(old.spec.node_name)
                is_assigned = bool(new.spec.node_name)
                if is_assigned and not was_assigned:
                    binds_confirmed([new])
                elif is_assigned:
                    self._update_pod_in_cache(old, new)
                    self._mark_chain_dirty()
                    self.queue.assigned_pod_updated(new)
                elif self._responsible(new) and not self._skip_pod_update(old, new):
                    self.queue.update(old, new)
            elif event == "delete":
                if pod.spec.node_name:
                    try:
                        self.cache.remove_pod(pod)
                    except ValueError:
                        pass
                    self._mark_chain_dirty()
                    self.queue.move_all_to_active_or_backoff_queue("PodDelete")
                else:
                    self.queue.delete(pod)
                    fwk = self.profiles.get(pod.spec.scheduler_name)
                    if fwk is not None:
                        fwk.reject_waiting_pod(pod.uid)

        def binds_confirmed(pods: List[api.Pod]) -> None:
            """The watch says ``pods`` are bound (possibly our own
            optimistic assumes): the cache's lock once and the queue's
            once, whatever their number."""
            if self.cache.confirm_pods(pods):
                self._mark_chain_dirty()   # a foreign writer bound one
            self.queue.pods_bound(pods)

        def on_pods(events) -> None:
            """One transaction of the store (client/store.py): its bind
            confirmations together, in runs, anything else one by one,
            in store order."""
            bound: List[api.Pod] = []
            for event, old, new in events:
                if (event == "update" and new.spec.node_name
                        and not old.spec.node_name):
                    bound.append(new)
                    continue
                if bound:
                    binds_confirmed(bound)
                    bound = []
                on_pod(event, old, new)
            if bound:
                binds_confirmed(bound)

        def on_node(event: str, old, new) -> None:
            if event == "add":
                self.cache.add_node(new)
                self._mark_chain_dirty()
                self.queue.move_all_to_active_or_backoff_queue("NodeAdd")
            elif event == "update":
                self.cache.update_node(old, new)
                self._mark_chain_dirty()
                if self._node_scheduling_properties_changed(old, new):
                    self.queue.move_all_to_active_or_backoff_queue("NodeUpdate")
            elif event == "delete":
                try:
                    self.cache.remove_node(old)
                except ValueError:
                    pass
                self._mark_chain_dirty()

        def on_moveable(kind: str):
            def handler(event: str, old, new) -> None:
                self.queue.move_all_to_active_or_backoff_queue(f"{kind}{event.title()}")
            return handler

        # the scheduler takes a transaction whole, so it has confirmed
        # every bind of one before any per-event subscriber hears of the
        # first (client/store.py)
        s.subscribe("Pod", on_pods, batched=True)
        s.subscribe("Node", on_node)
        for kind in ("PersistentVolume", "PersistentVolumeClaim",
                     "StorageClass", "Service", "CSINode"):
            s.subscribe(kind, on_moveable(kind))

    def _mark_chain_dirty(self) -> None:
        """Bump the chain event sequence AFTER the cache mutation it
        describes (capture happens before the snapshot, so this ordering
        guarantees a counted bump's mutation is snapshot-visible; a
        late bump only over-invalidates)."""
        with self._chain_lock:
            self._chain_seq += 1

    def _chain_enabled(self, fwk) -> bool:
        # mesh profiles chain too (PR 14): materialize_assigned is a
        # concat/pad/scatter program — the kernel class the partitioner
        # lowers correctly at every mesh shape (unlike the auction loop,
        # which needed the explicit shard_map rewrite) — and without the
        # chain the depth-k executor serializes on mesh profiles, which
        # would leave the double-buffered batch upload nothing to
        # overlap with
        return (self.config.mode == "gang"
                and getattr(self.config, "chain_cycles", False))

    def _add_pod_to_cache(self, pod: api.Pod) -> None:
        try:
            self.cache.add_pod(pod)
        except ValueError:
            # already assumed on another node etc. — cache resolves
            pass

    def _update_pod_in_cache(self, old: api.Pod, new: api.Pod) -> None:
        try:
            self.cache.update_pod(old, new)
        except ValueError:
            self._add_pod_to_cache(new)

    def _responsible(self, pod: api.Pod) -> bool:
        # reference: eventhandlers.go:333 responsibleForPod
        return pod.spec.scheduler_name in self.profiles

    @staticmethod
    def _skip_pod_update(old: api.Pod, new: api.Pod) -> bool:
        """reference: eventhandlers.go:311 skipPodUpdate — only
        resourceVersion/status-ish changes."""
        return (old.spec == new.spec
                and old.metadata.labels == new.metadata.labels
                and old.metadata.annotations == new.metadata.annotations)

    @staticmethod
    def _node_scheduling_properties_changed(old: api.Node, new: api.Node) -> bool:
        # reference: eventhandlers.go:471
        return (old.spec.unschedulable != new.spec.unschedulable
                or old.metadata.labels != new.metadata.labels
                or old.spec.taints != new.spec.taints
                or old.status.allocatable != new.status.allocatable)

    # ------------------------------------------------------------------ cycle

    def _next_rng(self):
        self._rng_counter += 1
        return self._jax.random.PRNGKey(self._rng_counter)

    def schedule_pending(self, max_batch: Optional[int] = None,
                         timeout: float = 0.0) -> List[ScheduleOutcome]:
        """Run ONE batched scheduling cycle: pop up to batch_size pods and
        schedule them.  Returns outcomes (the test/introspection surface).
        The serving loop (run/serve_forever) just calls this repeatedly."""
        # between two cycles nothing is open on this thread (the caller
        # has dropped the last cycle's outcomes): the one place the
        # survivors are handed to the permanent generation
        heap = self._heap
        if heap is not None:
            # armed, with a cycle just behind: the teardown's second child
            since = utrace.teardown_mark()
            did = heap.boundary(self.cycle_count)
            if since is not None:
                utrace.teardown_child(
                    utrace.HEAP_SPAN, since,
                    handoff=int(did != uheap.NOTHING),
                    sweep=int(did == uheap.SWEPT))
        max_batch = max_batch or self.config.batch_size
        if self.extenders:
            # extenders are a per-pod HTTP round trip; keep the reference's
            # strictly serial semantics (scheduler.go:510 pops one pod)
            max_batch = 1
        if (self.config.pipeline_cycles and not self.extenders
                and self.config.mode == "gang"
                and getattr(self.config, "chain_cycles", False)):
            # the depth-k pipelined executor (kubetpu/pipeline.py):
            # prepare(k+1) overlaps device(k) and commit/bind(k-1)
            return self._pipeline.drain(max_batch, timeout)
        by_profile, pop = self._pop_grouped(max_batch, timeout)
        if not by_profile:
            return []
        return self._schedule_groups(by_profile, pop)

    def flush_pipeline(self) -> List[ScheduleOutcome]:
        """Commit every in-flight pipelined cycle, oldest first (used at
        shutdown and by callers that need every outcome materialized
        now)."""
        return self._pipeline.flush()

    def _pop_grouped(self, max_batch: int, timeout: Optional[float]):
        """The cycle's ``pop`` phase: pop a batch, drop the pods that
        need no scheduling, group the rest by profile (one device program
        per framework config).  Returns (by_profile, the phase or None):
        still open, it is handed to the Trace of the cycle it fed, which
        closes it as the cycle's own first phase opens."""
        pop = utrace.begin_pop()
        # the recorder's stamps (a capture alone opens a phase too)
        timed = pop is not None and pop.cpu0 is not None
        waited0 = self.queue.pop_wait_s if pop is not None else 0.0
        t_queue = utrace.wallclock() if timed else 0.0
        qpods = self.queue.pop_batch(max_batch, timeout=timeout)
        t_group = utrace.wallclock() if timed else 0.0
        by_profile = self._group_by_profile(qpods)
        if timed:
            # the rest of the pop in its two parts: the queue (its wait
            # included: queue_s - wait_s is its own work) and the skip
            # check a pod with the grouping
            pop.args.update(queue_s=round(t_group - t_queue, 6),
                            group_s=round(utrace.wallclock() - t_group, 6))
        if pop is not None:
            pop.args.update(
                wait_s=round(self.queue.pop_wait_s - waited0, 6),
                popped=len(qpods),
                skipped=len(qpods) - sum(map(len, by_profile.values())))
            if not by_profile:
                pop.close()          # no cycle follows to close it
        return by_profile, pop

    def _group_by_profile(self, qpods: List[QueuedPodInfo]
                          ) -> Dict[str, List[QueuedPodInfo]]:
        by_profile: Dict[str, List[QueuedPodInfo]] = {}
        for qp in qpods:
            if self._skip_pod_schedule(qp.pod):
                continue
            by_profile.setdefault(qp.pod.spec.scheduler_name, []).append(qp)
        return by_profile

    def _schedule_batch(self, qpods: List[QueuedPodInfo]) -> List[ScheduleOutcome]:
        return self._schedule_groups(self._group_by_profile(qpods))

    def _schedule_groups(self, by_profile: Dict[str, List[QueuedPodInfo]],
                         pop=None) -> List[ScheduleOutcome]:
        start = utrace.wallclock()
        outcomes: List[ScheduleOutcome] = []
        for name, group in by_profile.items():
            fwk = self.profiles[name]
            # the pop fed every group; its span rides the first's record
            outcomes.extend(self._schedule_group(fwk, group, pop=pop))
            pop = None
        if self.metrics:
            self.metrics.observe_cycle(len(outcomes),
                                       utrace.wallclock() - start)
        return outcomes

    def _skip_pod_schedule(self, pod: api.Pod) -> bool:
        """reference: scheduler.go:691 skipPodSchedule — deleted or
        assumed-and-updated-only pods."""
        current = self.store.get_pod(pod.namespace, pod.metadata.name)
        if current is None or current.metadata.deletion_timestamp is not None:
            return True
        if self.cache.is_assumed_pod(pod):
            return True
        return False

    def _schedule_group(self, fwk: Framework, qpods: List[QueuedPodInfo],
                        pop=None) -> List[ScheduleOutcome]:
        prep, outcomes = self._prepare_group(fwk, qpods, pop=pop)
        if prep is None:
            return outcomes
        if self.extenders:
            try:
                return outcomes + self._schedule_with_extenders(
                    fwk, prep.live, prep.states, prep.node_infos,
                    prep.cluster, prep.batch, prep.cfg, prep.host_ok_dev,
                    prep.cycle_ctx, score_bias=prep.score_bias)
            finally:
                prep.trace.finish()
        with prep.trace.phase("dispatch"):
            try:
                res = self._dispatch_group(prep)
            except Exception as e:  # device/backend fault: recover, never
                # lose the batch (the old behavior leaked the popped pods
                # when the serving loop swallowed the exception)
                out = self._recover_cycle(prep, repr(e), "dispatch-error")
                prep.trace.finish(recovered="dispatch-error")
                return outcomes + out
        return outcomes + self._finish_group(prep, res)

    @staticmethod
    def _host_relevance(fwk: Framework, qpods: List[QueuedPodInfo],
                        classes: Optional[PodClasses] = None
                        ) -> Dict[str, Tuple[bool, bool]]:
        """ONE walk of the host filter plugins' relevance predicates per
        CLASS of pods (``classes``: classify_pods of exactly these pods,
        made here where not given): uid -> (any relevant, any relevant
        beyond the device-covered volume family).  Every consumer — the
        pipelined drain's serialize decision, the host-mask loop gate, and
        the commit-time re-check — shares this map instead of re-walking
        (the round-5 ADVICE double-walk finding)."""
        from .state.volumes import DEVICE_COVERED_PLUGINS
        if classes is None:
            classes = classify_pods([qp.pod for qp in qpods])
        per_class: List[Tuple[bool, bool]] = []
        for r in classes.reps:
            names = [p.name() for p in fwk.relevant_plugins(
                fwk.host_filter_plugins, qpods[r].pod)]
            per_class.append((bool(names), any(
                name not in DEVICE_COVERED_PLUGINS for name in names)))
        return {qp.pod.uid: per_class[k]
                for qp, k in zip(qpods, classes.class_of)}

    def _prepare_group(self, fwk: Framework, qpods: List[QueuedPodInfo],
                       uncommitted: Optional[List[PreparedCycle]] = None,
                       relevance: Optional[Dict[str, Tuple[bool, bool]]]
                       = None, pop=None,
                       classes: Optional[PodClasses] = None):
        """Host half of a cycle, up to (but excluding) the device dispatch:
        snapshot, PreFilter, tensorize-or-chain, host filter masks,
        nominated overlay -- the phases ``snapshot``, ``prefilter``,
        ``tensorize``, ``host-masks`` of the cycle's partition
        (utils/trace.py).  Returns (PreparedCycle | None, early outcomes).
        uncommitted: EVERY dispatched-but-uncommitted pipelined cycle (the
        depth-k executor's in-flight ring) whose device buffers must
        survive this prepare (gates delta donation).  pop: the ``pop``
        phase that fed this cycle (_pop_grouped), for its Trace to close
        and record.  classes: classify_pods of ``qpods``, where the caller
        has grouped them already (made here otherwise): what prepare
        computes from a pending pod's namespace, labels, annotations,
        owner references and spec alone -- its PodInfo, its batch row, its
        default spread selector, which host plugins care about it -- it
        computes once a class."""
        # queue depths ride the cycle record; the read takes the queue's
        # condition lock, so it is GATED on the recorder being armed (the
        # disarmed hot path must take no new locks)
        depths = (self.queue.depths()
                  if utrace.flight_recorder() is not None else None)
        trace = Trace(utrace.CYCLE_TRACE, profile=fwk.profile_name,
                      pods=len(qpods), queue_depths=depths, pop=pop)
        trace.phase("snapshot")
        # capture the event sequence BEFORE snapshotting: a chain is only
        # reusable if no event has landed since the state it embeds
        with self._chain_lock:
            chain_seq0 = self._chain_seq
        # ---- snapshot (reference: generic_scheduler.go:155 snapshot())
        self.cache.update_snapshot(self.snapshot)
        node_infos = self.snapshot.node_info_list
        n_nodes = len(node_infos)
        trace.step("Snapshotting scheduler cache and node infos done")
        if trace.rec is not None:
            # the moment the snapshot was taken: what a tie-set check on
            # this cycle's own binds has to place the cluster at
            trace.rec.meta["snapshot_t"] = round(utrace.wallclock(), 6)
            trace.note(nodes=n_nodes, pods_copied=self.snapshot.pods_copied)
        trace.phase("prefilter", pods=len(qpods))
        if self.metrics:
            self.metrics.cache_size.set(n_nodes, "nodes")
            self.metrics.cache_size.set(self.cache.pod_count(), "pods")
            self.metrics.cache_size.set(len(self.cache.assumed_pods),
                                        "assumed_pods")

        # ---- host PreFilter + basic checks; build scheduleable set
        states: Dict[str, CycleState] = {}
        live: List[QueuedPodInfo] = []
        outcomes: List[ScheduleOutcome] = []
        with trace.stage("classify", pods=len(qpods)):
            if classes is None:
                classes = classify_pods([qp.pod for qp in qpods])
            pre_relevant = [
                fwk.relevant_plugins(fwk.host_pre_filter_plugins,
                                     qpods[r].pod) for r in classes.reps]
        timed: list = []   # the PreFilter point's observations, a run a row
        for qp, k in zip(qpods, classes.class_of):
            state = CycleState()
            if pre_relevant[k]:
                st = fwk.run_pre_filter_plugins(
                    state, qp.pod, relevant=pre_relevant[k], sink=timed)
                if not st.is_success():
                    outcomes.append(self._fail(
                        fwk, qp, state, "",
                        st.message() or "prefilter failed",
                        preemption_may_help=not st.code
                        == Code.UNSCHEDULABLE_AND_UNRESOLVABLE))
                    self._record_decision(qp.pod, "unschedulable",
                                          message=st.message()
                                          or "prefilter failed",
                                          blocking=["PreFilter"])
                    continue
            states[qp.pod.uid] = state
            live.append(qp)
        if fwk.metrics is not None:
            # observed as one; for a pod that no PreFilter plugin cares
            # about the point does nothing, in no time, and was not run
            fwk.metrics.framework_extension_point_duration.observe_many(
                timed + [(0.0, "PreFilter", "Success")]
                * (len(qpods) - len(timed)))
        if not live:
            trace.finish()
            return None, outcomes
        if n_nodes == 0:
            for qp in live:
                outcomes.append(self._fail(fwk, qp, states[qp.pod.uid], "",
                                           "0/0 nodes are available",
                                           preemption_may_help=False))
                self._record_decision(qp.pod, "unschedulable",
                                      message="0/0 nodes are available")
            trace.finish()
            return None, outcomes

        # ---- tensorize, or reuse the CHAINED cluster: the previous gang
        # cycle's materialized tensors already ARE this snapshot (no
        # unaccounted event landed), so skip the full rebuild entirely
        trace.phase("tensorize")
        with trace.stage("classify", pods=len(live)):
            pods = [qp.pod for qp in live]
            if len(live) != len(qpods):
                classes = classify_pods(pods)    # PreFilter failed some
            reps = classes.reps
            pinfos = class_pod_infos(pods, classes)
            rep_infos = [pinfos[r] for r in reps]
            # inside the cycle: a Service added since the last one is seen
            rep_sels = [self.store.default_spread_selector(pods[r])
                        for r in reps]
            spread_sels = [rep_sels[k] for k in classes.class_of]
        # nominated pods join the tensor world too (labels/terms for the
        # addNominatedPods topology overlay) — their vocab must be interned
        # before snapshot arrays are sized
        nom_pinfos = [PodInfo(pod) for pod, _ in self.queue.all_nominated()]
        journal_input = None
        with self._chain_lock:
            chain = self._chain
        use_chain = (chain is not None and chain["seq"] == chain_seq0
                     and self._chain_enabled(fwk)
                     and chain["profile"] == fwk.profile_name
                     and chain["n_nodes"] == n_nodes)
        if use_chain:
            builder = chain["builder"]
            builder.intern_pending(rep_infos + nom_pinfos)
            if _vocab_caps(builder.table) != chain["caps"]:
                use_chain = False   # vocab bucket overflow: rebuild
        if use_chain:
            cluster = chain["cluster"]
            chain_pod_uids = chain["pod_uids"]
            if ujournal.journal() is not None:
                # journal provenance: this cycle's cluster is the
                # previous committed cycle's auction, materialized at
                # the pad buckets the chain recorded
                journal_input = ("chain", chain.get("pads"))
        else:
            # incremental tensorization (state/delta.py): the resident
            # device cluster is brought up to date by a bounded scatter
            # over the cycle's dirty rows; a full build() runs only on the
            # DeltaTensorizer's blessed resync path.  The chain branch
            # above is the zero-delta special case of the same pipeline.
            delta = self._delta.get(fwk.profile_name)
            if delta is None:
                delta = DeltaTensorizer(
                    hard_pod_affinity_weight=fwk.hard_pod_affinity_weight,
                    mesh=self._mesh)
                self._delta[fwk.profile_name] = delta
            # in-place buffer donation is only safe when NO
            # dispatched-but-uncommitted pipelined cycle still reads the
            # resident buffers (its commit-side preemption wave and
            # decision audit dispatch against prep.cluster).  ONE source
            # of truth per call: the depth-k drain passes its in-flight
            # ring explicitly; callers that don't (the synchronous path,
            # scatter-recovery re-prepares) fall back to the executor's
            # ring so a prepare racing parked cycles can never donate
            # either.
            inflight = (uncommitted if uncommitted is not None
                        else self._pipeline.inflight_preps())
            # the donation-withholding set: every dispatched-but-
            # uncommitted ring cycle PLUS every prepared cycle whose
            # double-buffered batch upload is still in flight (its
            # dispatch hasn't consumed the resident yet) — one gate,
            # fed from both sources
            donate = delta.safe_to_donate(
                [p.cluster for p in inflight if p is not None]
                + [p.cluster for p in self._undispatched])
            # pending/nominated pods intern inside refresh (a compacting
            # resync re-interns them into its fresh table); the delta's
            # pod-row floor stands on the batch alone
            cluster, dstats = delta.refresh(
                node_infos, pending=rep_infos + nom_pinfos, donate=donate,
                batch=len(pinfos))
            # AFTER refresh: a compacting resync swaps the builder
            builder = delta.builder
            rec = trace.rec
            if rec is not None:
                for name, st0, st1 in dstats.spans:
                    rec.record_span(name, st0, st1,
                                    parent_id=trace.span_id,
                                    delta_rows=dstats.delta_rows,
                                    **dstats.span_args.get(name, {}))
                rec.meta["delta_rows"] = dstats.delta_rows
                # the (dirty-node, churned-pod) row buckets the scatter
                # program was dispatched with: a new pair is a compile
                rec.meta["delta_buckets"] = list(dstats.delta_buckets)
                rec.meta["resync"] = dstats.resync
                if dstats.resync:
                    rec.event("resync", parent_id=trace.span_id,
                              reason=dstats.reason)
            if dstats.resync:
                self.resync_count += 1
                if dstats.reason == "verify-divergence":
                    # the anti-entropy verifier caught device residents
                    # diverging from the host mirror and forced the
                    # targeted full resync — a recovery, not churn
                    self._record_recovery("verify-resync",
                                          reason=dstats.reason)
            elif dstats.delta_rows > 0:
                # zero-dirty cycles (retry churn with no cache events) ran
                # no scatter — counting them would drag the row p50 to 0
                # and diverge from the span-based traceview digest
                self.delta_rows.append(dstats.delta_rows)
                self.delta_cycle_count += 1
            with trace.stage("row-maps") as maps_span:
                # this cycle's existing-pod rows in row order: a copy, the
                # next refresh moves the tensorizer's own
                chain_pod_uids = delta.pod_uid_list()
                if maps_span is not None:
                    maps_span.args["pod_rows"] = len(delta.pod_row)
                # journal capture seam (state/delta.py): the exact resync
                # snapshot / delta tables / zero-dirty marker this refresh
                # applied — None when the journal is disarmed
                journal_input = delta.take_capture()
                if journal_input is not None:
                    if (fwk.profile_name in self._journal_force_anchor
                            and journal_input[0] != "resync"):
                        # THIS profile's discarded cycle applied a
                        # delta/resync capture that never journaled, so
                        # its resident is ahead of the journal stream —
                        # re-anchor from the mirror (bit-equal to the
                        # resident after any successful refresh, the
                        # anti-entropy verifier's invariant).  The
                        # capture format is owned by ONE site: the
                        # tensorizer's own resync seam
                        delta._capture_resync()
                        journal_input = delta.take_capture()
                    self._journal_force_anchor.discard(fwk.profile_name)
            with self._chain_lock:
                self._chain = None
        pb = PodBatchBuilder(builder.table)
        with trace.stage("batch-build", pods=len(pinfos)) as build_span:
            batch = self._jax.tree.map(
                np.asarray, pb.build(pinfos, spread_selectors=spread_sels,
                                     classes=classes))
            # valid DoNotSchedule constraint rows the builder compiled,
            # and the ScheduleAnyway ones beside them
            spread_rows = int(batch.spread.valid.sum())
            soft_spread_rows = int(batch.spread_soft.valid.sum())
            term_sets_live = live_term_sets(batch)
            # valid required pod-affinity term rows of the incoming pods
            ra_rows = int(batch.ra.valid.sum())
            # valid required node-selector terms of the batch's rows (after
            # the class gather), and the unique selector rows compiled for
            # them: the node-affinity match is O(unique x nodes)
            rna_rows = int(batch.rna_valid.sum())
            rna_unique = int(batch.rna_sel.sel_valid.sum())
            if build_span is not None:
                build_span.args["spread_rows"] = spread_rows
                build_span.args["ra_rows"] = ra_rows
                build_span.args["rna_rows"] = rna_rows
                build_span.args["rna_unique"] = rna_unique
                build_span.args["term_sets_live"] = term_sets_live
                build_span.args["pod_classes"] = pb.pod_classes
                build_span.args["rows_built"] = pb.rows_built
        batch_dev = None
        if self._mesh is not None:
            # DOUBLE-BUFFERED transfer: start the sharded upload of this
            # wave's batch NOW — in the depth-k drain, prepare(k+1) runs
            # while wave k's auction occupies the device, so the
            # host->device transfer is issued behind the running program
            # instead of serializing in front of k+1's dispatch (whether
            # the copy actually overlaps the program on the chip: not
            # measured).  device_put is async: the span below is the
            # ISSUE of the upload (its arg says so), not the transfer,
            # and traceview shows it inside the prepare stage — i.e.
            # UNDER the previous wave's device window
            from .parallel import mesh as pmesh
            t_up = utrace.wallclock()
            batch_dev = pmesh.shard_batch(batch, self._mesh)
            if trace.rec is not None:
                t_issued = utrace.wallclock()
                nbytes = sum(np.asarray(x).nbytes
                             for x in self._jax.tree.leaves(batch))
                trace.rec.record_span("batch-upload", t_up, t_issued,
                                      parent_id=trace.span_id,
                                      bytes=int(nbytes),
                                      issue_s=round(t_issued - t_up, 6),
                                      double_buffered=True)
        B = batch.valid.shape[0]
        N = cluster.allocatable.shape[0]
        if trace.rec is not None:
            # the pod-axis bucket this cycle dispatches in — the unit
            # tools/kubeaot --prune works in (buckets the recorder never
            # saw are dead ladder rungs, dropped from the artifact set)
            trace.rec.meta["pod_bucket"] = int(cluster.pod_valid.shape[0])
            # rows of that axis in use, and the bytes of the cluster the
            # cycle dispatches on (from shapes)
            trace.rec.meta["pod_rows_live"] = (
                len(chain_pod_uids) - chain_pod_uids.count(None)
                if use_chain else len(delta.pod_row))
            trace.rec.meta["cluster_device_bytes"] = cluster.nbytes
            # the existing-term rows (Et filter, Es score) the auction's
            # match and contractions run over, padding included
            trace.rec.meta["term_buckets"] = [
                int(cluster.filter_terms.valid.shape[0]),
                int(cluster.score_terms.valid.shape[0])]
            # the batch's hard spread constraints: valid rows, and the
            # constraint (C) and unique-selector (Us) buckets the
            # auction's recount runs over, padding included
            trace.rec.meta["spread_constraints"] = spread_rows
            trace.rec.meta["spread_soft_constraints"] = soft_spread_rows
            trace.rec.meta["required_affinity_terms"] = ra_rows
            trace.rec.meta["node_affinity_terms"] = rna_rows
            trace.rec.meta["node_affinity_unique_selectors"] = rna_unique
            # the term sets whose existing-pod products this batch's
            # auction runs (ops/kernels.py _if_live); the rest are gated off
            trace.rec.meta["term_sets_live"] = term_sets_live
            # the batch's pod classes and the rows the builder built for
            # them (the pod count where the classes were too many to share)
            trace.rec.meta["pod_classes"] = pb.pod_classes
            trace.rec.meta["rows_built"] = pb.rows_built
            trace.rec.meta["spread_buckets"] = [
                int(batch.spread.valid.shape[1]),
                int(batch.spread.sel.sel_valid.shape[0])]
            trace.note(delta_rows=trace.rec.meta.get("delta_rows", 0),
                       delta_buckets=trace.rec.meta.get("delta_buckets", []),
                       pod_bucket=trace.rec.meta["pod_bucket"])
            # the batch in batch order: row i of the bind table is
            # batch_pods[i]
            trace.rec.meta["batch_pods"] = [qp.pod.metadata.name
                                            for qp in live]
        trace.phase("host-masks")

        # ---- host filter plugins -> mask fed into the device program.
        # ONE walk of the host plugins' relevance predicates per pod per
        # CYCLE (_host_relevance) computes BOTH "any relevant" (the
        # commit-time re-check gate) and "any relevant beyond the
        # device-covered volume family" (the per-node Python loop gate).
        # The pipelined drain walks it up front for its serialize
        # decision and passes the map in, so the walk never runs twice.
        from .state.volumes import (DEVICE_COVERED_PLUGINS,
                                    build_volume_overlay, volume_mask)
        if relevance is None:
            relevance = self._host_relevance(fwk, live, classes)
        host_relevant: Dict[str, bool] = {}
        host_uncovered: Dict[str, bool] = {}
        for qp in live:
            rel, unc = relevance[qp.pod.uid]
            host_relevant[qp.pod.uid] = rel
            host_uncovered[qp.pod.uid] = unc
        # the volume family evaluates ON DEVICE (state/volumes.py): one
        # jitted [B, N] mask replaces ~B x N Python filter calls for
        # PVC-heavy batches.  The host plugins still run at commit time
        # (host_relevant above), preserving intra-batch race checks.
        enabled_hosts = {p.name() for p in fwk.host_filter_plugins}
        vol_mask_dev = None
        if (DEVICE_COVERED_PLUGINS & enabled_hosts
                and any(pods[r].spec.volumes for r in reps)):
            overlay = build_volume_overlay(
                self.store, node_infos, [qp.pod for qp in live],
                builder.table, enabled_hosts)
            if overlay is not None:
                vol_mask_dev = volume_mask(cluster, overlay)
        host_ok = np.ones((B, N), bool)
        any_host = False
        host_reject: Dict[str, Dict[str, int]] = {}
        audit = self.decisions.enabled
        for i, qp in enumerate(live):
            if not host_relevant[qp.pod.uid]:
                continue
            if vol_mask_dev is not None and not host_uncovered[qp.pod.uid]:
                continue   # every relevant host filter is device-covered
            any_host = True
            state = states[qp.pod.uid]
            for j, ni in enumerate(node_infos):
                st = fwk.run_filter_plugins(state, qp.pod, ni)
                host_ok[i, j] = st.is_success()
                if audit and not st.is_success():
                    # per-reason node counts for the decision audit
                    # ("4 nodes rejected by host filter: too many volumes")
                    counts = host_reject.setdefault(qp.pod.uid, {})
                    for r in (st.reasons or ["host filter failed"]):
                        counts[r] = counts.get(r, 0) + 1
        # ---- nominated-pods two-pass overlay (addNominatedPods,
        # generic_scheduler.go:530,594-612): equal/higher-priority pods
        # nominated by preemption reserve their nominated nodes' capacity
        # AND contribute topology terms (anti-affinity/spread).  The mask
        # stays a DEVICE array — it is consumed on the device, and a
        # [B, N] readback would be a second host sync per cycle
        batch_topo_keys = self._batch_topo_keys(builder.table, rep_infos)
        nom_mask = self._nominated_overlay_mask(fwk, builder, cluster,
                                                batch, live, node_infos,
                                                batch_topo_keys)
        # host Score/NormalizeScore plugins -> a [B, N] score bias the
        # device program adds before selectHost (framework.go:579-656).
        # Normalization runs over ALL valid nodes pre-dispatch (the
        # reference normalizes over the filtered set — a documented
        # deviation that keeps the single-readback design)
        score_bias = None
        if fwk.host_score_plugins:
            node_names = [ni.node_name for ni in node_infos]
            nodes_raw = [ni.node for ni in node_infos]
            bias = np.zeros((B, N), np.float32)
            any_bias = False
            scored = [bool(fwk.relevant_plugins(fwk.host_score_plugins,
                                                pods[r])) for r in reps]
            for i, (qp, k) in enumerate(zip(live, classes.class_of)):
                if not scored[k]:
                    continue
                state = states[qp.pod.uid]
                st = fwk.run_pre_score_plugins(state, qp.pod, nodes_raw)
                if not st.is_success():
                    # the reference fails the pod's cycle here; we keep
                    # the pod but drop its host scores (documented
                    # deviation — a failing PreScore must not abort the
                    # whole batch)
                    import logging
                    logging.getLogger("kubetpu").warning(
                        "prescore failed for %s: %s; host scores dropped",
                        qp.pod.metadata.name, st.message())
                    continue
                try:
                    plugin_scores = fwk.run_host_score_plugins(
                        state, qp.pod, node_names)
                except RuntimeError as e:
                    import logging
                    logging.getLogger("kubetpu").warning(
                        "host score failed for %s: %s; scores dropped",
                        qp.pod.metadata.name, e)
                    continue
                for vals in plugin_scores.values():
                    bias[i, :len(vals)] += vals
                    any_bias = True
            if any_bias:
                score_bias = self._jax.numpy.asarray(bias)
        host_ok_dev = None
        if any_host:
            host_ok_dev = self._jax.numpy.asarray(host_ok)
        if vol_mask_dev is not None:
            host_ok_dev = (vol_mask_dev if host_ok_dev is None
                           else host_ok_dev & vol_mask_dev)
        if nom_mask is not None:
            host_ok_dev = (nom_mask if host_ok_dev is None
                           else host_ok_dev & nom_mask)
        cfg = programs.ProgramConfig(
            filters=fwk.tensor_filters, scores=fwk.tensor_scores,
            hostname_topokey=max(builder.table.topokey.get(api.LABEL_HOSTNAME), 0),
            plugin_args=fwk.tensor_plugin_args(builder.table),
            # 0 => the reference's adaptive default (types.go:251); only
            # the sequential replay consumes it — gang needs the global view
            percentage_of_nodes_to_score=(
                self.config.percentage_of_nodes_to_score
                if self.config.percentage_of_nodes_to_score > 0 else 0),
            # restrict the same-pair matmuls to the keys THIS batch's terms
            # actually use (superset contract, see ProgramConfig)
            active_topo_keys=batch_topo_keys,
            # the batch's score-side term sets with a valid row: the
            # auction splices them into score_terms for its later rounds
            batch_score_sets=batch_score_sets(
                term_sets_live, fwk.hard_pod_affinity_weight),
            hard_pod_affinity_weight=float(fwk.hard_pod_affinity_weight))
        from .preemption import CycleContext
        cycle_ctx = CycleContext(
            builder=builder, cluster=cluster, cfg=cfg,
            node_infos=node_infos, batch=batch,
            row_of={qp.pod.uid: i for i, qp in enumerate(live)})
        # the cluster's existing-pod rows in row order (delta-resident and
        # chained clusters' rows diverge from node_infos build order;
        # preemption victim masking needs the true mapping and derives it
        # from this when first asked)
        cycle_ctx.pod_uids = chain_pod_uids
        trace.step("Tensorizing snapshot and pod batch done")

        from .framework.types import pod_with_affinity
        # per-round topology re-evaluation only pays off when some pod
        # actually carries topology terms; a term-free batch takes the
        # cheaper static path (round-0 verdicts are provably invariant)
        needs_topo = (any(pod_with_affinity(pods[r])
                          or pods[r].spec.topology_spread_constraints
                          for r in reps)
                      # service/RC replicas score via
                      # DefaultPodTopologySpread even without explicit
                      # terms — they need intra-batch placements too
                      or any(s is not None for s in rep_sels))
        if trace.rec is not None:
            trace.rec.meta["needs_topo"] = int(needs_topo)
            # valid batch rows the auction splices into score_terms
            # (models/gang.py _extend_cluster; none without needs_topo)
            trace.rec.meta["score_terms_spliced"] = (
                score_rows_spliced(batch, cfg.batch_score_sets)
                if needs_topo else 0)
        prep = PreparedCycle(
            fwk=fwk, trace=trace, chain_seq0=chain_seq0,
            node_infos=node_infos, states=states, live=live, pinfos=pinfos,
            builder=builder, cluster=cluster, batch=batch,
            host_relevant=host_relevant, host_ok_dev=host_ok_dev, cfg=cfg,
            cycle_ctx=cycle_ctx, classes=classes, needs_topo=needs_topo,
            used_chain=use_chain, chain_pod_uids=chain_pod_uids,
            score_bias=score_bias, host_reject=host_reject,
            relevance=relevance, journal_input=journal_input,
            batch_dev=batch_dev)
        if batch_dev is not None:
            # until _dispatch_group consumes the upload, this cycle's
            # dispatch still reads the resident cluster — withhold
            # donation (see __init__._undispatched)
            self._undispatched.append(prep)
        # host-masks stays open: the dispatch phase closes it as it opens
        return prep, outcomes

    def _dispatch_group(self, prep: PreparedCycle, extra_uncommitted: int = 0):
        """Device dispatch of a prepared cycle (async: returns once the
        program is enqueued), plus the speculative chain materialize so
        the NEXT cycle can tensorize against this cycle's placements
        before they commit.
        extra_uncommitted: pods dispatched in earlier cycles whose commits
        (and so cache.pod_count()) have not landed yet — the pipelined
        drain passes the in-flight cycle's batch size so the chain bucket
        guard sees the same fresh-rebuild estimate the synchronous path
        would."""
        fwk, cluster, batch, cfg = (prep.fwk, prep.cluster, prep.batch,
                                    prep.cfg)
        host_ok_dev, cycle_ctx = prep.host_ok_dev, prep.cycle_ctx
        n_nodes = len(prep.node_infos)
        # the double-buffered upload is consumed by THIS dispatch; the
        # cycle graduates to the ordinary in-flight donation set
        # (identity filter: PreparedCycle holds arrays, == is undefined)
        self._undispatched = [p for p in self._undispatched
                              if p is not prep]
        if prep.batch_dev is not None:
            # consume the pre-uploaded sharded batch (shard_batch passes
            # committed-sharding arrays through untouched)
            batch = prep.batch_dev
        # deadline-guard anchor + chaos seam (utils/chaos.py "dispatch"):
        # an injected error models the device dying under the program; an
        # injected stall models a hung device — both recovered by
        # _recover_cycle via the guarded call sites / readback.
        # wallclock (utils/trace.py): the deadline is a
        # duration-by-subtraction — an NTP step must not corrupt it
        prep.dispatch_t0 = utrace.wallclock()
        if self._dispatch_deadline > 0:
            # idempotent singleton; first call installs the
            # jax.monitoring listener, later calls are a lock + read
            from .utils.sanitize import install_compile_timer
            prep.compile_snap = install_compile_timer().snapshot()
        uchaos.raise_or_stall("dispatch")
        seq_start = 0
        # ---- device: one program for the whole group (scan or auction)
        if self.config.mode == "gang":
            needs_topo = prep.needs_topo
            if self._mesh is not None:
                from .parallel import mesh as pmesh
                res = pmesh.sharded_schedule_gang(
                    cluster, batch, cfg, self._next_rng(), self._mesh,
                    host_ok=host_ok_dev,
                    intra_batch_topology=needs_topo,
                    score_bias=prep.score_bias)
            else:
                from .models.gang import run_auction
                res = run_auction(
                    cluster, batch, cfg, self._next_rng(),
                    host_ok=host_ok_dev,
                    intra_batch_topology=needs_topo,
                    score_bias=prep.score_bias)
            # the auction already produced per-pod verdict rows; share them
            # lazily so preemption can skip its candidates pass without the
            # scheduler paying a multi-MB transfer it may never need
            cycle_ctx.set_lazy_verdicts(res.feasible0, res.unresolvable)
        else:
            start = seq_start = self._next_start_node_index % max(n_nodes, 1)
            if self._mesh is not None:
                from .parallel import mesh as pmesh
                res = pmesh.sharded_schedule_sequential(
                    cluster, batch, cfg, self._next_rng(), self._mesh,
                    hard_pod_affinity_weight=float(
                        fwk.hard_pod_affinity_weight),
                    host_ok=host_ok_dev,
                    start_index=start,
                    score_bias=prep.score_bias)
            else:
                res = schedule_sequential(
                    cluster, batch, cfg, self._next_rng(),
                    hard_pod_affinity_weight=float(
                        fwk.hard_pod_affinity_weight),
                    host_ok=host_ok_dev,
                    start_index=start,
                    score_bias=prep.score_bias)
        if ujournal.journal() is not None:
            # journal provenance: the RNG fold counter this dispatch
            # consumed (_next_rng bumped it inside the call above) and
            # the sequential rotating start — exactly what kubereplay
            # feeds back into the same program
            prep.journal_rng = self._rng_counter
            prep.journal_start = seq_start
        # request the packed readback transfer BEFORE enqueueing the chain
        # materialize, so the copy can start as soon as the auction ends
        # and the materialize overlaps the host's commit loop (whether a
        # transfer requested later would queue behind the materialize on
        # the chip: not measured)
        try:
            res.packed.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        # ---- speculative chain (gang only): materialize this cycle's
        # placements into the next cycle's cluster NOW, on device, so the
        # pipelined drain can tensorize+dispatch cycle k+1 while this
        # cycle's commit loop runs.  _finish_group discards it if a commit
        # fails (the device-side placements then diverged from reality).
        chain_ok = self.config.mode == "gang" and self._chain_enabled(fwk)
        if chain_ok:
            from .utils.intern import pow2_bucket
            B_cap = batch.valid.shape[0]
            p_next = int(cluster.pod_valid.shape[0]) + B_cap
            # never chain into a BIGGER pod-axis bucket than a fresh
            # rebuild would use: pow2 slack compounds across cycles
            # (bucket + B -> next bucket) and a rebuild compacts it —
            # chaining past this line doubles HBM for nothing.  (Estimated
            # pre-commit: cache.pod_count() excludes this cycle's assumes,
            # so allow one batch of slack plus any in-flight cycle's.)
            fresh_p = pow2_bucket(self.cache.pod_count() + extra_uncommitted
                                  + 2 * B_cap)
            if pow2_bucket(p_next) > fresh_p:
                chain_ok = False
        if chain_ok:
            from .models.gang import materialize_assigned
            ta = batch.raa.valid.shape[1]
            e_next = int(cluster.filter_terms.valid.shape[0]) + B_cap * ta
            next_cluster = materialize_assigned(
                cluster, batch, res.chosen,
                res.requested, res.nz, res.ports_used,
                pad_pods_to=pow2_bucket(p_next),
                pad_terms_to=pow2_bucket(e_next),
                extend_score_terms=True,
                hard_pod_affinity_weight=float(
                    fwk.hard_pod_affinity_weight))
            uids = list(prep.chain_pod_uids)
            uids.extend(pi.pod.uid for pi in prep.pinfos)
            uids.extend([None] * (B_cap - len(prep.pinfos)))  # batch padding
            uids.extend([None] * (pow2_bucket(p_next) - len(uids)))
            with self._chain_lock:
                self._chain = dict(builder=prep.builder,
                                   cluster=next_cluster,
                                   pod_uids=uids, seq=prep.chain_seq0,
                                   caps=_vocab_caps(prep.builder.table),
                                   profile=fwk.profile_name,
                                   n_nodes=n_nodes,
                                   # journal provenance: the pad buckets
                                   # a chained successor must feed back
                                   # into materialize_assigned to rebuild
                                   # this cluster bit-exactly
                                   pads=(pow2_bucket(p_next),
                                         pow2_bucket(e_next)))
        elif self.config.mode == "gang":
            with self._chain_lock:
                self._chain = None
        return res

    # ----------------------------------------------------------- recovery

    def _record_recovery(self, kind: str, **fields) -> None:
        """Every self-healing event lands here: the serving loop keeps
        running, and the incident stays visible — in recovery_log, in
        scheduler_recoveries_total, and in the --once exit code."""
        self.recovery_log.append(
            dict(fields, kind=kind, cycle=self.cycle_count))
        self.recoveries_total += 1
        if self.metrics is not None:
            self.metrics.recoveries.inc(kind)

    def _recover_cycle(self, prep: PreparedCycle, reason: str,
                       kind: str) -> List[ScheduleOutcome]:
        """Self-healing path for a cycle whose device dispatch errored or
        blew its deadline (kind: "dispatch-error" / "dispatch-deadline").
        Three moves, in order:

        1. DEMOTE: an armed AOT runtime disarms with the reason recorded
           (AOT -> trace; the persistent-cache/trace ladder still
           serves).  The demotion is an incident INSTANT on the cycle's
           flight record, visible in /debug/flightz and traceview.
        2. INVALIDATE the device residents this dispatch may have
           poisoned: the speculative chain and the profile's
           DeltaTensorizer cluster — the next cycle resyncs from a fresh
           host walk (the blessed "initial" path).
        3. REQUEUE the cycle's pods through the backoff queue.  Recovery
           runs strictly BEFORE the commit loop, so nothing was
           reserved, assumed or bound: pods are never lost and never
           double-bound — they simply retry on the traced program.

        Never raises: the serving loop must survive any fault this
        handles."""
        import logging
        logging.getLogger("kubetpu").warning(
            "cycle recovery (%s): %s; %d pods requeued", kind, reason,
            len(prep.live))
        demoted = []
        from .utils import aot as _aot
        if _aot.active_runtime() is not None:
            _aot.disarm(reason="%s: %s" % (kind, reason[:200]))
            demoted.append("aot->trace")
        with self._chain_lock:
            self._chain = None
            self._chain_seq += 1
        self._delta.pop(prep.fwk.profile_name, None)
        for qp in prep.live:
            try:
                self.queue.add_unschedulable_if_not_present(
                    qp, qp.scheduling_cycle)
            except ValueError:
                pass
        # unschedulable -> backoff/active now (per-pod backoff paces the
        # retry); without the move the pods would wait for the periodic
        # leftover flush
        self.queue.move_all_to_active_or_backoff_queue("DispatchRecovery")
        self._deadline_grace = 2
        if self._heap is not None:
            self._heap.want_sweep()
        self._record_recovery(kind, reason=reason, pods=len(prep.live),
                              demoted=demoted)
        if prep.trace.rec is not None:
            prep.trace.rec.event(
                "backend-demotion" if demoted else "dispatch-recovery",
                kind=kind, reason=reason[:256],
                demoted=",".join(demoted))
        err = f"dispatch recovered ({kind}): pod requeued"
        return [ScheduleOutcome(pod=qp.pod, node="", err=err)
                for qp in prep.live]

    def _readback_guarded(self, prep: PreparedCycle, res):
        """(packed, None) on success; (None, recovery outcomes) when the
        readback raised — async dispatch errors surface HERE, at the
        cycle's only device sync — or when dispatch-to-readback wall
        time exceeded the configured deadline.  Either way the cycle is
        discarded pre-commit and recovered (_recover_cycle)."""
        if prep.parked_t:
            # time parked in the in-flight ring = caller think time
            # between schedule_pending calls — exempt from the deadline
            prep.host_exempt_s += utrace.wallclock() - prep.parked_t
            prep.parked_t = 0.0
        try:
            packed = self._readback_group(prep, res)
        except Exception as e:
            out = self._recover_cycle(prep, repr(e), "dispatch-error")
            prep.trace.finish(recovered="dispatch-error")
            return None, out
        dl = self._dispatch_deadline
        if dl > 0 and prep.dispatch_t0:
            if self._deadline_grace > 0:
                self._deadline_grace -= 1
            else:
                elapsed = (utrace.wallclock() - prep.dispatch_t0
                           - prep.host_exempt_s)
                compiled = False
                if prep.compile_snap is not None:
                    # a cycle that paid ANY XLA compile or cache load is
                    # exempt wholesale: the deadline gates steady-state
                    # DEVICE health, and demoting a backend over a
                    # legitimate first-compile would latch the whole
                    # process off its fast paths.  (Tracing/lowering
                    # time has no jax.monitoring event, so subtracting
                    # measured seconds under-exempts — the any-activity
                    # check is the robust form.  A device hang on a
                    # compile cycle is caught one cycle later.)
                    from .utils.sanitize import install_compile_timer
                    d = install_compile_timer().snapshot()
                    compiled = any(d[k] != prep.compile_snap[k]
                                   for k in d)
                if not compiled and elapsed > dl:
                    out = self._recover_cycle(
                        prep, "dispatch+readback %.3fs > deadline %.3fs"
                        % (elapsed, dl), "dispatch-deadline")
                    prep.trace.finish(recovered="dispatch-deadline")
                    return None, out
        return packed, None

    def _finish_group(self, prep: PreparedCycle, res) -> List[ScheduleOutcome]:
        """Readback + commit half of a cycle.  The packed readback is the
        cycle's ONLY device->host sync point."""
        packed, recovered = self._readback_guarded(prep, res)
        if packed is None:
            # the cycle never happened as far as state goes: its pods are
            # requeued and its residents invalidated; a later pipelined
            # cycle dispatched against its chain must also re-run
            self._last_commit_failed = True
            self._sync_flight_dropped()
            return recovered
        with prep.trace.phase("commit"):
            out = self._commit_group(prep, packed)
            # finish() inside the phase: handing the record over is the
            # commit's tail, and the phase closes as the record lands
            if self.config.mode == "gang":
                # per-cycle auction rounds as cycle meta: the benchmark's
                # readers and traceview's digest column take it from here
                prep.trace.finish(auction_rounds=self.last_gang_rounds)
            else:
                prep.trace.finish()
        self._sync_flight_dropped()
        return out

    def _readback_group(self, prep: PreparedCycle, res) -> np.ndarray:
        """ONE device->host readback per cycle: the packed [3B+1] i32 view
        (chosen | n_feasible | all_unresolvable | rounds / next_start).
        Every device->host copy is a host sync, so everything the host
        needs rides one small array and the big tensors (requested,
        masks) stay on device for chaining / lazy preemption verdicts;
        the pipelined drain issues this BEFORE dispatching the next
        cycle so the wait covers this cycle's program only.

        SYNC_PROBE (chip_smoke.py "sync-probe"; one v5e, PR 23, medians
        of 5, run_auction 1024 x 5000): block_until_ready on
        ``res.packed`` returns only after the program is done (21.6 ms,
        against 21.8 ms for np.asarray alone), and np.asarray of the
        already-computed 12 KB vector then costs 0.70 ms."""
        with prep.trace.phase("packed-readback", ann="readback") as sp:
            t_dev = utrace.wallclock()
            packed = np.asarray(res.packed)
            wait = utrace.wallclock() - t_dev
            if sp is not None:
                # per-span device-wait attribution: the readback is the
                # cycle's only observable device sync
                sp.args["device_wait_s"] = round(wait, 6)
            if prep.trace.rec is not None and hasattr(
                    res, "capacity_deferred"):
                # armed only: the auction's own count (GangResult), a
                # second, 4-byte copy that waits for nothing, the program
                # being done
                prep.trace.rec.meta["capacity_deferred"] = int(
                    res.capacity_deferred)
                if prep.trace.rec.meta.get("spread_soft_constraints"):
                    # the same for K.spread_soft_skew, where the batch
                    # carries a ScheduleAnyway constraint
                    prep.trace.rec.meta["spread_soft_skew"] = int(
                        res.soft_spread_skew)
                if (prep.trace.rec.meta.get("spread_constraints")
                        and res.spread_late_admits is not None):
                    # and for the admissions the round-start rule would
                    # have held back, where the batch carries a
                    # DoNotSchedule constraint
                    prep.trace.rec.meta["spread_late_admits"] = int(
                        res.spread_late_admits)
                if (prep.trace.rec.meta.get("required_affinity_terms")
                        and res.affinity_bootstrap_admits is not None):
                    # and for the pods the self-match bootstrap admitted,
                    # where the batch carries a required affinity term
                    prep.trace.rec.meta["affinity_bootstrap_admits"] = int(
                        res.affinity_bootstrap_admits)
        self.device_wait_s += wait
        return packed

    def _commit_group(self, prep: PreparedCycle,
                      packed: np.ndarray) -> List[ScheduleOutcome]:
        """Serving thread only (_finish_group for the serial loop, the
        pipelined executor's drain for a ring of cycles), inside the
        cycle's ``commit`` phase.  The loop assumes the cycle's pods in
        scan order and collects their binds; as it ends they go to the
        binder lane as ONE job (_hand_over), never a hand-over a pod.
        One job at the loop's end, not chunks as the loop runs: the lane
        then works under the next cycle's pop, prepare and readback
        instead of taking the interpreter from this loop.

        Consecutive pods whose commit is their assume alone (placed, no
        host filter to re-check, no Reserve / Unreserve / Permit plugin
        that cares about their class: Framework.commits_bare) are
        committed as a RUN, a step at a time over all of them
        (_commit_run); any other placed pod is committed by _commit, all
        steps a pod, between the run before it and the run after it, so
        what it re-checks is a cache in which every earlier pod of the
        batch is assumed.  Read off the pods and the cycle's verdicts,
        never a knob.  A cycle whose binds cannot ride the lane (no
        ``async_binding``, a job that is not ``lane_ok``) has no runs:
        those binds run inside _commit.

        Armed, the loop's split lands on that phase's span as SUMS --
        recheck_s, reserve_s, assume_s, permit_s, submit_s (the stamps
        and the appends, plus the hand-over), records_s (decision audit,
        the cycle context's notes, the outcomes), pods, batched (those of
        ``pods`` a run committed), loop_s, loop_cpu_s -- not as a span a
        pod; _hand_over adds bind_jobs, binds_pooled and
        handover_wait_s.  loop_s is the loop's last stamp less its first,
        so the six sums add up to it."""
        fwk, trace = prep.fwk, prep.trace
        live, states, pinfos = prep.live, prep.states, prep.pinfos
        node_infos, cycle_ctx = prep.node_infos, prep.cycle_ctx
        n_nodes = len(node_infos)
        B = prep.batch.valid.shape[0]
        self.cycle_count += 1
        outcomes: List[ScheduleOutcome] = []
        if self.config.mode != "gang":
            self._next_start_node_index = int(packed[3 * B])
        else:
            # auction round count (diagnostics; the cycle meta reads it)
            self.last_gang_rounds = int(packed[3 * B])
        # one .tolist() per field: the commit loop below reads every entry,
        # and plain Python ints beat a numpy scalar box per access at 4k
        # pods/cycle (kubelint host-sync audit)
        chosen = packed[:B][:len(live)].tolist()
        n_feas = packed[B:2 * B][:len(live)].tolist()
        unres = (packed[2 * B:3 * B][:len(live)] != 0).tolist()
        trace.step("Computing predicates and priorities on device done")

        # ---- commit each placement in scan order; failures DEFER until
        # every commit has landed so all preemption attempts share one
        # verdict refresh against the final committed state (N failed pods
        # cost one [B, N] pass, not N)
        deferred = []  # (outcome index, qp, state, message, may_help)
        commit_failed = False
        audit = self.decisions.enabled
        flight = trace.rec
        # durable cycle journal (utils/journal.py): reserve this cycle's
        # record id up front (the record itself appends after the commit
        # loop, once the outputs and audit summary exist).  Disarmed: one
        # attribute read
        jr = ujournal.journal()
        jr_seq = jr.next_seq() if jr is not None else 0
        # the split of the loop below, armed only: seconds summed per
        # step over the cycle's pods, each stamp closing one step and
        # opening the next (so the sums cover the loop): a stamp a step
        # a run, five a pod outside one -- _commit takes four, the loop's
        # tail the fifth.
        # acc = [recheck, reserve, assume, permit, submit, records, last
        # stamp]; disarmed it is None and no clock is read
        acc = None
        if flight is not None:
            flight.alloc_binds(len(live))
            loop_cpu0 = time.thread_time()
            loop_t0 = time.perf_counter()
            acc = [0.0, 0.0, 0.0, 0.0, 0.0, 0.0, loop_t0]
        job = (self._new_bind_job(fwk, flight) if self._async_binding
               else None)
        # which classes' pods may ride a run, asked once a class
        class_of, walk_s = prep.classes.class_of, (0.0, 0.0)
        bare = [False] * len(prep.classes.reps)
        if job is not None and job.lane_ok:
            bare, walk_s = fwk.commits_bare(
                [live[r].pod for r in prep.classes.reps])
            if acc is not None:
                _lap(acc, 1)
                permit_s = min(walk_s[1], acc[1])   # two clocks
                acc[1] -= permit_s
                acc[3] += permit_s
        host_relevant = prep.host_relevant
        run: List[int] = []     # the open run's rows of ``live``
        batched = run_failed = 0
        for i, qp in enumerate(live):
            if chosen[i] < 0:
                outcomes.append(None)
                deferred.append((i, qp, states[qp.pod.uid],
                                 f"0/{n_nodes} nodes are available",
                                 not unres[i]))
                continue
            if bare[class_of[i]] and not host_relevant[qp.pod.uid]:
                outcomes.append(None)
                run.append(i)
                batched += 1
                continue
            if run:
                run_failed += self._commit_run(prep, run, chosen, n_feas,
                                               outcomes, job, acc)
                run = []
            node_name = node_infos[chosen[i]].node_name
            outcome = self._commit(fwk, qp, states[qp.pod.uid], node_name,
                                   n_feas[i], pinfo=pinfos[i],
                                   host_relevant=host_relevant[qp.pod.uid],
                                   flight=flight, row=i, acc=acc,
                                   job=job)
            if outcome.node:
                # preemption for pods failing later in this batch must see
                # this placement (CycleContext.cluster_now overlay)
                cycle_ctx.note_commit(i, chosen[i])
                if audit:
                    self._record_decision(qp.pod, "scheduled",
                                          node=outcome.node,
                                          n_feasible=n_feas[i])
            else:
                commit_failed = True
                if audit:
                    self._record_decision(qp.pod, "unschedulable",
                                          message=outcome.err or
                                          "commit failed",
                                          n_feasible=n_feas[i])
            outcomes.append(outcome)
            if acc is not None:
                _lap(acc, 5)
        if run:
            run_failed += self._commit_run(prep, run, chosen, n_feas,
                                           outcomes, job, acc)
        commit_failed = commit_failed or bool(run_failed)
        if batched and fwk.metrics is not None:
            # what the per-pod walk gives the extension-point histogram
            # for these pods: one Reserve observation a pod and one Permit
            # a pod that was assumed, the class walk's seconds shared out
            fwk.metrics.framework_extension_point_duration.observe_many(
                [(walk_s[0] / batched, "Reserve", "Success")] * batched
                + [(walk_s[1] / batched, "Permit", "Success")]
                * (batched - run_failed))
            if acc is not None:
                _lap(acc, 1)
        if job is not None:
            self._hand_over(job)
        if acc is not None:
            _lap(acc, 4)
            trace.note(
                recheck_s=round(acc[0], 6), reserve_s=round(acc[1], 6),
                assume_s=round(acc[2], 6), permit_s=round(acc[3], 6),
                submit_s=round(acc[4], 6), records_s=round(acc[5], 6),
                pods=len(live) - len(deferred), batched=batched,
                loop_s=round(acc[-1] - loop_t0, 6),
                loop_cpu_s=round(time.thread_time() - loop_cpu0, 6))
        # ---- preemption WAVE: every preemption-eligible FitError of this
        # cycle is served by ONE batched what-if (preemption.preempt_wave)
        # instead of a per-pod candidates pass + what-if dispatch each.
        # The per-pod PostFilter below short-circuits on the recorded wave
        # verdicts; if the wave itself fails, it records nothing and the
        # per-pod path serves as the fallback.  Only safe when
        # DefaultPreemption is the first PostFilter plugin — an earlier
        # custom plugin could resolve the failure without evictions.
        wave_pods = [qp.pod for _, qp, _, _, mh in deferred if mh]
        if wave_pods and self.preemptor is not None:
            from .plugins.intree import DefaultPreemption
            pf = fwk.post_filter_plugins
            if pf and isinstance(pf[0], DefaultPreemption):
                try:
                    with trace.stage("preemption-wave",
                                     pods=len(wave_pods)):
                        self.preemptor.preempt_wave(fwk, cycle_ctx,
                                                    wave_pods)
                except Exception:
                    import logging
                    logging.getLogger("kubetpu").warning(
                        "preemption wave failed; per-pod fallback",
                        exc_info=True)
        # ---- decision audit: fold the per-(pod, node) filter verdicts
        # already computed on device into per-plugin attribution for the
        # failed pods (one extra packed readback, only on cycles that have
        # failures and only with the audit enabled)
        audit_rows = {}
        if deferred and audit:
            # retry-churn dedup: a persistent unschedulable tail fails
            # with the SAME pod set against the SAME state every cycle —
            # re-dispatching the audit would add a device sync per cycle
            # (and, pipelined, serialize behind the in-flight dispatch)
            # for identical answers.  Reuse holds only when nothing
            # placed, nothing evicted and no preemption wave ran this
            # cycle; any success or wave recomputes.
            uids = frozenset(qp.pod.uid for _, qp, _, _, _ in deferred)
            cached = self._audit_cache
            if (cached is not None and cached[0] == uids
                    and cycle_ctx.commits == 0 and not wave_pods):
                audit_rows = cached[1]
            else:
                with trace.stage("decision-audit", pods=len(deferred)):
                    audit_rows = self._audit_failures(
                        prep, [qp for _, qp, _, _, _ in deferred])
                self._audit_cache = (uids, audit_rows)
        # pod_verdicts refreshes the shared verdicts lazily on the FIRST
        # preemption attempt that needs them (and the min-priority gate may
        # skip them entirely), so no eager refresh here
        for idx, qp, state, msg, mh in deferred:
            outcomes[idx] = self._fail(fwk, qp, state, "", msg,
                                       preemption_may_help=mh,
                                       cycle=cycle_ctx)
            if audit:
                info = audit_rows.get(qp.pod.uid, {})
                self._record_decision(
                    qp.pod, "unschedulable", message=msg,
                    nominated_node=qp.pod.status.nominated_node_name or "",
                    host_reasons=prep.host_reject.get(qp.pod.uid),
                    **info)
        # a commit-path failure invalidates the speculative chain (and any
        # later cycle already dispatched against it — the pipelined drain
        # reads _last_commit_failed and re-runs that cycle)
        self._last_commit_failed = commit_failed
        if commit_failed and self.config.mode == "gang":
            with self._chain_lock:
                self._chain = None
        if jr is not None:
            # one self-contained replayable record per committed cycle;
            # ANY failure (unpicklable capture, disk, injected chaos)
            # degrades to a counted drop — recording never fails a cycle
            try:
                self._journal_append(jr, jr_seq, prep, packed, outcomes,
                                     audit_rows)
            except Exception:
                jr.note_drop()
                import logging
                logging.getLogger("kubetpu").warning(
                    "cycle journal record %d dropped", jr_seq,
                    exc_info=True)
        trace.step("Committing placements done")
        trace.log_if_long()
        return outcomes

    def _journal_note_discard(self, prep: PreparedCycle) -> None:
        """A prepared cycle is being discarded without committing (the
        pipelined executor's chain-break/scatter re-prepare).  If its
        journal capture carried resident state (delta scatter or resync),
        that state is now applied on device but will never be journaled
        — flag the PROFILE's next journaled cycle to re-anchor.
        Chain/noop captures carry no resident state and need nothing.
        Also drops the cycle from the double-buffer donation-withholding
        set — a discarded cycle's upload will never be consumed."""
        self._undispatched = [p for p in self._undispatched
                              if p is not prep]
        if prep.journal_input is not None \
                and prep.journal_input[0] in ("delta", "resync"):
            self._journal_force_anchor.add(prep.fwk.profile_name)

    def _journal_append(self, jr, jr_seq: int, prep: PreparedCycle,
                        packed: np.ndarray, outcomes, audit_rows) -> None:
        """Assemble + append one cycle-journal record (armed only; the
        caller degrades any failure to a counted drop).  The record is
        SELF-CONTAINED: everything tools/kubereplay needs to re-execute
        this cycle's device program and bit-match its packed output —
        inputs (cluster provenance, pod batch, cfg, masks, RNG fold),
        outputs (packed vector, placements, verdict summary) and the
        linkage ids into the flight-recorder seq and decision-audit
        cycle.  ``host_ok``/``score_bias`` are read back from device
        here — an armed journal pays that transfer on the commit side;
        the disarmed path never reaches this method."""
        mode = self.config.mode
        fwk, live = prep.fwk, prep.live
        kind, payload = prep.journal_input or ("unknown", None)
        hard_w = float(fwk.hard_pod_affinity_weight)
        placements: Dict[str, str] = {}
        blocking: Dict[str, int] = {}
        scheduled = failed = 0
        for i, qp in enumerate(live):
            o = outcomes[i] if i < len(outcomes) else None
            node = o.node if o is not None else ""
            placements[qp.pod.metadata.name] = node
            if node:
                scheduled += 1
            else:
                failed += 1
                info = (audit_rows or {}).get(qp.pod.uid, {})
                for plugin in info.get("blocking", []):
                    blocking[plugin] = blocking.get(plugin, 0) + 1
        host_reasons: Dict[str, int] = {}
        for counts in prep.host_reject.values():
            for reason, n in counts.items():
                host_reasons[reason] = host_reasons.get(reason, 0) + n
        flight = prep.trace.rec
        record = {
            "v": ujournal.RECORD_VERSION,
            "seq": jr_seq,
            "cycle": self.cycle_count,
            "ts": time.time(),
            "mode": mode,
            "profile": fwk.profile_name,
            # ---- inputs ----
            "input": kind,
            "input_payload": payload,
            "batch": prep.batch,
            "cfg": prep.cfg,
            "host_ok": (np.asarray(prep.host_ok_dev)
                        if prep.host_ok_dev is not None else None),
            "score_bias": (np.asarray(prep.score_bias)
                           if prep.score_bias is not None else None),
            "needs_topo": bool(prep.needs_topo),
            "rng_counter": int(prep.journal_rng),
            "start_index": int(prep.journal_start),
            "hard_pod_affinity_weight": hard_w,
            "mesh": self._mesh is not None,
            "vocab_sig": _vocab_caps(prep.builder.table),
            "n_nodes": len(prep.node_infos),
            # node row order only on anchor records — delta/chain records
            # provably keep it (a node-set change forces a resync)
            "node_names": ([ni.node_name for ni in prep.node_infos]
                           if kind == "resync" else None),
            "config_digest": ujournal.config_digest(
                mode, fwk.profile_name, prep.cfg, hard_w),
            # ---- outputs ----
            "packed": np.asarray(packed),
            "rounds": (self.last_gang_rounds if mode == "gang" else 0),
            "pods": [(qp.pod.metadata.name, qp.pod.namespace, qp.pod.uid)
                     for qp in live],
            "placements": placements,
            "verdicts": {"scheduled": scheduled, "failed": failed,
                         "blocking": blocking,
                         "host_reasons": host_reasons},
            # ---- linkage ----
            "links": {
                "flight_seq": int(flight.seq) if flight is not None else 0,
                "decision_cycle": self.cycle_count,
                "ring_slot": int(prep.ring_slot),
                "pipeline_depth": int(self._pipeline.depth
                                      if self.config.pipeline_cycles
                                      else 1),
            },
        }
        jr.append(record)

    def _sync_journal_metrics(self) -> None:
        """Fold the armed journal's counters into scheduler_journal_*
        (serving thread only, like _sync_chaos_metrics); disarmed this
        is one attribute read."""
        jr = ujournal.journal()
        if jr is None or self.metrics is None:
            return
        records, dropped = jr.counters()
        seen_r, seen_d = self._journal_seen
        if records > seen_r:
            self.metrics.journal_records.inc(amount=records - seen_r)
        if dropped > seen_d:
            self.metrics.journal_dropped.inc(amount=dropped - seen_d)
        self._journal_seen = (max(records, seen_r), max(dropped, seen_d))
        self.metrics.journal_bytes.set(jr.disk_bytes())

    def _sync_chaos_metrics(self) -> None:
        """Fold the armed chaos registry's fire counts into
        scheduler_faults_injected_total (serving thread only, like
        _sync_flight_dropped); disarmed this is one attribute read."""
        reg = uchaos.active()
        if reg is None or self.metrics is None:
            return
        for point, n in reg.counts().items():
            seen = self._chaos_seen.get(point, 0)
            if n > seen:
                self.metrics.faults_injected.inc(point, amount=n - seen)
                self._chaos_seen[point] = n

    def _sync_flight_dropped(self) -> None:
        """Fold new flight-recorder ring drops into the monotonic metric
        counter — called right after each cycle record commits (serving
        thread only, so the seen-count needs no lock)."""
        self._sync_chaos_metrics()
        self._sync_journal_metrics()
        fr = utrace.flight_recorder()
        if fr is None or self.metrics is None:
            return
        dropped = fr.dropped()
        if dropped > self._flight_dropped_seen:
            self.metrics.flight_recorder_dropped.inc(
                amount=dropped - self._flight_dropped_seen)
        if dropped != self._flight_dropped_seen:
            # < happens when the ring was cleared/re-armed mid-run
            self._flight_dropped_seen = dropped

    def _schedule_with_extenders(self, fwk: Framework, live, states,
                                 node_infos, cluster, batch, cfg,
                                 host_ok, cycle_ctx=None,
                                 score_bias=None) -> List[ScheduleOutcome]:
        """Extender path (reference: generic_scheduler.go:497
        findNodesThatPassExtenders + :674-706 extender Prioritize combine):
        one batch filter+score on device, then per pod the HTTP webhooks
        refine feasibility/scores and selection happens host-side.
        score_bias: the [B, N] weighted host Score plugin totals from
        _prepare_group — added to the device totals BEFORE the extender
        Prioritize combine, so host Score plugins are honored identically
        with and without extenders configured."""
        from .extender import MAX_EXTENDER_PRIORITY, ExtenderError
        import random
        if self._mesh is not None:
            from .parallel import mesh as pmesh
            res = pmesh.sharded_filter_and_score(cluster, batch, cfg,
                                                 self._mesh, host_ok=host_ok)
        else:
            res = programs.filter_and_score(
                cluster, batch, cfg,
                self._jax.numpy.asarray(host_ok) if host_ok is not None
                else None)
        # ONE batched readback for the whole group, then Python lists: a
        # per-element float(scores[i, j]) in the per-pod loop below would
        # box B x N numpy scalars (and, pre-np.asarray, would cost one
        # device sync each — the kubelint host-sync/loop-readback trap)
        feasible = np.asarray(res.feasible).tolist()
        score_arr = np.asarray(res.scores)
        if score_bias is not None:
            score_arr = score_arr + np.asarray(score_bias)
        scores = score_arr.tolist()
        self.cycle_count += 1
        n_nodes = len(node_infos)
        row_of_node = {ni.node_name: j for j, ni in enumerate(node_infos)}
        outcomes: List[ScheduleOutcome] = []
        for i, qp in enumerate(live):
            state = states[qp.pod.uid]
            row_feas = feasible[i]
            names = [node_infos[j].node_name for j in range(n_nodes)
                     if row_feas[j]]
            # the device mask is pre-batch: re-check fit against the LIVE
            # node usage (includes earlier same-batch assumes) so two pods
            # in one extender batch cannot oversubscribe a node
            pod_res = PodInfo(qp.pod).resource
            names = [n for n in names
                     if self._fits_live(pod_res, self.cache.node_fit_view(n))]
            row_scores = scores[i]
            dev_score = {node_infos[j].node_name: row_scores[j]
                         for j in range(n_nodes) if row_feas[j]}
            exts = [e for e in self.extenders if e.is_interested(qp.pod)]
            err = None
            ext_info: Dict[str, str] = {}
            try:
                for e in exts:
                    before = len(names)
                    names, _ = e.filter(qp.pod, names)
                    # an extender may echo names outside the device-feasible
                    # set (stale cache, typo) — never let those through
                    names = [n for n in names if n in dev_score]
                    ext_info[e.url_prefix or "extender"] = (
                        f"filter {before} -> {len(names)} nodes")
                    if not names:
                        break
            except ExtenderError as ex:
                err = f"extender filter failed: {ex}"
            if err is not None:
                outcomes.append(self._fail(fwk, qp, state, "", err,
                                           preemption_may_help=False))
                self._record_decision(qp.pod, "unschedulable", message=err,
                                      extenders=ext_info)
                continue
            if not names:
                outcomes.append(self._fail(
                    fwk, qp, state, "", f"0/{n_nodes} nodes are available",
                    cycle=cycle_ctx))
                self._record_decision(
                    qp.pod, "unschedulable",
                    message=f"0/{n_nodes} nodes are available",
                    extenders=ext_info)
                continue
            combined = {n: 0.0 for n in names}
            try:
                for e in exts:
                    for n, s in e.prioritize(qp.pod, names).items():
                        if n in combined:
                            combined[n] += s
            except ExtenderError as ex:
                outcomes.append(self._fail(fwk, qp, state, "",
                                           f"extender prioritize failed: {ex}",
                                           preemption_may_help=False))
                self._record_decision(
                    qp.pod, "unschedulable",
                    message=f"extender prioritize failed: {ex}",
                    extenders=ext_info)
                continue
            scale = fw.MAX_NODE_SCORE / MAX_EXTENDER_PRIORITY
            totals = {n: dev_score[n] + combined[n] * scale for n in names}
            best = max(totals.values())
            ties = [n for n in names if totals[n] == best]
            self._rng_counter += 1
            node_name = random.Random(self._rng_counter).choice(ties)

            binders = [e for e in exts if e.is_binder()]
            binder = None
            if binders:
                def binder(pod, node, _b=binders[0]):
                    _b.bind(pod, node)
            outcome = self._commit(fwk, qp, state, node_name, len(names),
                                   binder_override=binder)
            if outcome.node and cycle_ctx is not None:
                cycle_ctx.note_commit(i, row_of_node[node_name])
            self._record_decision(
                qp.pod, "scheduled" if outcome.node else "unschedulable",
                node=outcome.node, message=outcome.err or "",
                n_feasible=len(names), extenders=ext_info)
            outcomes.append(outcome)
        return outcomes

    @staticmethod
    def _batch_topo_keys(table, pinfos) -> Tuple[int, ...]:
        """Topology-key vocab ids used by the batch's term sets — the
        static key set the same-pair matmul kernels iterate (a superset of
        every key in the batch per the ProgramConfig contract; cluster-side
        term paths use per-term pair gathers and need no key loop)."""
        keys = set()
        get = table.topokey.get
        for pi in pinfos:
            for term in pi.required_affinity_terms:
                keys.add(get(term.topology_key))
            for term in pi.required_anti_affinity_terms:
                keys.add(get(term.topology_key))
            for w in pi.preferred_affinity_terms:
                keys.add(get(w.term.topology_key))
            for w in pi.preferred_anti_affinity_terms:
                keys.add(get(w.term.topology_key))
            for c in pi.pod.spec.topology_spread_constraints:
                keys.add(get(c.topology_key))
        keys.discard(-1)
        return tuple(sorted(keys))

    def _nominated_overlay_mask(self, fwk, builder, cluster, batch, live,
                                node_infos, batch_topo_keys=()):
        """[B, N] bool DEVICE array — False where a pod would not fit once
        equal-or-greater-priority NOMINATED pods are counted as running on
        their nominated nodes (reference: addNominatedPods,
        core/generic_scheduler.go:530; the overlay-free second pass is the
        main filter program).  Covers BOTH dimensions of AddPod: resource
        capacity (nominated_fit_mask) and topology terms — nominated pods'
        labels and required anti-affinity repel, and their label counts
        skew PodTopologySpread (nominated_topology_mask).  A nominated pod
        that is itself in the batch reserves capacity against every OTHER
        row, never its own; batch-member nominated pods are excluded from
        the topology overlay (per-row self-exclusion is not expressible in
        one pass — documented bounded deviation).  None when no nominated
        pod is relevant."""
        from .models.batch import build_nominated
        nominated = self.queue.all_nominated()
        if not nominated:
            return None
        uid_to_row = {qp.pod.uid: i for i, qp in enumerate(live)}
        node_row = {ni.node_name: j for j, ni in enumerate(node_infos)}
        entries = []
        for pod, nn in nominated:
            row = node_row.get(nn)
            if row is None:
                continue
            entries.append((PodInfo(pod), row, uid_to_row.get(pod.uid, -1)))
        if not entries:
            return None
        nom = build_nominated(entries, builder.table)
        mask = programs.nominated_fit_mask(cluster, batch, nom)

        # topology overlay: only when the profile runs topology filters and
        # some term could actually interact
        topo_filters = {"InterPodAffinity", "PodTopologySpread"}
        topo_entries = [(pi, row) for pi, row, sr in entries if sr < 0]
        if topo_entries and (topo_filters & set(fwk.tensor_filters)):
            from .framework.types import (pod_with_affinity,
                                          pod_with_required_anti_affinity)
            interacts = (
                any(pod_with_affinity(qp.pod)
                    or qp.pod.spec.topology_spread_constraints
                    for qp in live)
                or any(pod_with_required_anti_affinity(pi.pod)
                       for pi, _ in topo_entries))
            if interacts:
                jnp = self._jax.numpy
                nom_pb = PodBatchBuilder(builder.table).build(
                    [pi for pi, _ in topo_entries])
                nom_pb = self._jax.tree.map(np.asarray, nom_pb)
                M = np.asarray(nom_pb.valid).shape[0]
                rows = np.full((M,), -1, np.int32)
                prio = np.zeros((M,), np.int32)
                for i, (pi, row) in enumerate(topo_entries):
                    rows[i] = row
                    prio[i] = pi.pod.priority()
                active = tuple(sorted(
                    set(batch_topo_keys)
                    | set(self._batch_topo_keys(
                        builder.table, [pi for pi, _ in topo_entries]))))
                topo_mask = programs.nominated_topology_mask(
                    cluster, nom_pb, jnp.asarray(rows), jnp.asarray(prio),
                    batch, programs.ProgramConfig(
                        filters=fwk.tensor_filters, scores=(),
                        hostname_topokey=max(
                            builder.table.topokey.get(api.LABEL_HOSTNAME),
                            0),
                        active_topo_keys=active))
                mask = mask & topo_mask
        return mask

    @staticmethod
    def _fits_live(pod_res, view) -> bool:
        """NodeResourcesFit essentials against a live fit view
        (cache.node_fit_view: allocatable, requested, pod count;
        reference: noderesources/fit.go:194-267): pod count always, the
        standard channels and scalars only when requested."""
        if view is None:
            return False
        alloc, req, n_pods = view
        if n_pods + 1 > alloc.allowed_pod_number:
            return False
        r = pod_res
        if r.milli_cpu > 0 and r.milli_cpu > alloc.milli_cpu - req.milli_cpu:
            return False
        if r.memory > 0 and r.memory > alloc.memory - req.memory:
            return False
        if (r.ephemeral_storage > 0 and r.ephemeral_storage
                > alloc.ephemeral_storage - req.ephemeral_storage):
            return False
        for k, v in r.scalar_resources.items():
            if v > 0 and v > (alloc.scalar_resources.get(k, 0)
                              - req.scalar_resources.get(k, 0)):
                return False
        return True

    # ------------------------------------------------------------------ commit

    def _commit(self, fwk: Framework, qp: QueuedPodInfo, state: CycleState,
                node_name: str, n_feasible: int,
                binder_override=None, pinfo: Optional[PodInfo] = None,
                host_relevant: Optional[bool] = None,
                flight=None, row: int = -1,
                acc: Optional[List[float]] = None,
                job: Optional[BindJob] = None) -> ScheduleOutcome:
        """Serving thread only: Reserve, assume and Permit for one pod,
        in scan order, then its bind is handed on.  flight / row: the
        cycle's CycleRecord and this pod's row of its bind table (armed
        only).  acc: the commit loop's running split (_commit_group),
        None disarmed.  job: the cycle's hand-over, which _commit_group
        gives to the binder lane once its loop ends; a caller that
        commits one pod (the extender path) passes none and the pod is
        handed over here, as a job of one.  With ``async_binding=False``
        the bind runs here, before this returns."""
        pod = qp.pod
        if host_relevant is None:
            host_relevant = fwk.has_relevant_host_filters(pod)
        # Commit-time host-filter re-check: the pre-batch host_ok mask was
        # computed before any same-batch pod was assumed, so two same-batch
        # pods could exceed a host-checked per-node limit (e.g. attachable
        # volumes).  Re-validate against the cache's LIVE NodeInfo — which
        # includes earlier same-batch assumes — before reserving.  The
        # reference's serial loop gets this by construction
        # (scheduler.go:509: every pod filters against assumed state).
        if host_relevant:
            ni = self.cache.node_info(node_name)
            if ni is not None:
                st = fwk.run_filter_plugins(state, pod, ni)
                if not st.is_success():
                    # other nodes may still fit next cycle; don't preempt
                    # on a stale single-node verdict
                    return self._fail(fwk, qp, state, node_name,
                                      st.message() or
                                      "commit-time filter re-check failed",
                                      preemption_may_help=False)
            if acc is not None:
                _lap(acc, 0)
        # Reserve (reference: scheduler.go:586).  Commit-phase failures are
        # not FitErrors, so they never trigger preemption
        # (reference: scheduler.go:542 err type check).
        st = fwk.run_reserve_plugins(state, pod, node_name)
        if not st.is_success():
            fwk.run_unreserve_plugins(state, pod, node_name)
            return self._fail(fwk, qp, state, node_name, st.message(),
                              preemption_may_help=False)
        if acc is not None:
            _lap(acc, 1)

        # assume (reference: scheduler.go:435,593)
        assumed = _assumed(pod, node_name)
        try:
            self.cache.assume_pod(
                assumed,
                pinfo.with_pod(assumed) if pinfo is not None else None)
        except ValueError as e:
            fwk.run_unreserve_plugins(state, pod, node_name)
            return self._fail(fwk, qp, state, node_name, str(e),
                              preemption_may_help=False)
        if acc is not None:
            _lap(acc, 2)

        # Permit (reference: scheduler.go:608)
        st = fwk.run_permit_plugins(state, pod, node_name)
        if not st.is_success() and st.code != Code.WAIT:
            self._forget(assumed)
            fwk.run_unreserve_plugins(state, pod, node_name)
            return self._fail(fwk, qp, state, node_name, st.message(),
                              preemption_may_help=False)
        if acc is not None:
            _lap(acc, 3)

        # binding cycle (reference: scheduler.go:628 goroutine)
        if flight is not None:
            flight.stamp_bind(row, utrace.BIND_SUBMITTED)
        err = None
        if not self._async_binding:
            err = self._bind_cycle(fwk, qp, state, assumed, node_name,
                                   binder_override, flight, row)
        else:
            own = job is None
            if own:
                job = self._new_bind_job(fwk, flight)
            if (job.lane_ok and binder_override is None
                    and st.code != Code.WAIT):
                job.entries.append((fwk, qp, state, assumed, node_name,
                                    row))
            else:
                # this bind would block the lane (it waits on Permit or
                # on a network): it takes a pool thread of its own
                args = (fwk, qp, state, assumed, node_name,
                        binder_override, flight, row)
                try:
                    job.pooled.append(
                        self._bind_pool.submit(self._bind_cycle, *args))
                except RuntimeError:
                    # close() raced the serving loop and shut the pool
                    # down mid-cycle: bind synchronously so the placement
                    # still lands instead of panicking the cycle
                    err = self._bind_cycle(*args)
            if own:
                self._hand_over(job)
        if acc is not None:
            _lap(acc, 4)
        return ScheduleOutcome(pod=pod, node=node_name if err is None else "",
                               err=err, n_feasible=n_feasible)

    def _commit_run(self, prep: PreparedCycle, rows: List[int],
                    chosen: List[int], n_feas: List[int],
                    outcomes: List[Optional[ScheduleOutcome]],
                    job: BindJob, acc: Optional[List[float]]) -> int:
        """Serving thread only: the commit of ``rows`` (rows of
        ``prep.live`` in scan order, each placed on ``chosen[row]`` and
        bare as _commit_group reads it) a step at a time over all of
        them, where _commit runs all steps a pod: the assumed clones,
        ONE hold of the cache's lock, one stamp of the bind table, one
        extension of ``job``, one scatter into the cycle context's
        overlays, one write of the decision audit.  Fills ``outcomes`` at
        ``rows``.  A pod the cache refuses is failed as _commit fails it
        (no Unreserve: no such plugin cares about a bare pod) and the
        rest of the run stands; returns how many were.  acc as in
        _commit."""
        fwk, live, states = prep.fwk, prep.live, prep.states
        node_infos, pinfos = prep.node_infos, prep.pinfos
        flight = prep.trace.rec
        nodes = [node_infos[chosen[i]].node_name for i in rows]
        assumed = [_assumed(live[i].pod, node)
                   for i, node in zip(rows, nodes)]
        errs = self.cache.assume_pods_many(
            assumed, [pinfos[i].with_pod(a) for i, a in zip(rows, assumed)])
        if acc is not None:
            _lap(acc, 2)
        failed = len(errs) - errs.count(None)
        ok = rows if not failed else [
            i for i, err in zip(rows, errs) if err is None]
        if flight is not None:
            flight.stamp_binds(ok, utrace.BIND_SUBMITTED)
        job.entries.extend(
            (fwk, live[i], states[live[i].pod.uid], a, node, i)
            for i, a, node, err in zip(rows, assumed, nodes, errs)
            if err is None)
        if acc is not None:
            _lap(acc, 4)
        # preemption for pods failing later in this batch must see these
        # placements (CycleContext.cluster_now overlay)
        prep.cycle_ctx.note_commits(ok, [chosen[i] for i in ok])
        cycle = self.cycle_count
        decisions = [] if self.decisions.enabled else None
        for i, node, err in zip(rows, nodes, errs):
            qp = live[i]
            pod = qp.pod
            if err is None:
                outcomes[i] = ScheduleOutcome(pod=pod, node=node,
                                              n_feasible=n_feas[i])
            else:
                outcomes[i] = self._fail(fwk, qp, states[pod.uid], node, err,
                                         preemption_may_help=False)
            if decisions is not None:
                m = pod.metadata
                decisions.append(PodDecision(
                    m.name, m.namespace, m.uid,
                    "scheduled" if err is None else "unschedulable",
                    node=outcomes[i].node, message=err or "",
                    n_feasible=n_feas[i], cycle=cycle))
        if decisions:
            self.decisions.record_many(decisions)
        if acc is not None:
            _lap(acc, 5)
        return failed

    def _new_bind_job(self, fwk: Framework, flight=None) -> BindJob:
        """An empty hand-over for binds through ``fwk``.  Whether they may
        ride the lane at all is read off what they will meet: a Bind
        client outside this process, or a chaos bind fault that can still
        fire, blocks, so those binds keep the pool and its parallelism."""
        return BindJob(flight, lane_ok=fwk.binds_in_process()
                       and not uchaos.armed("bind"))

    def _hand_over(self, job: BindJob) -> None:
        """The serving thread gives ``job`` to the binder lane, once the
        pods it holds are assumed.  Counts the hand-over on the open
        phase's span (``commit``: args ``bind_jobs``, ``binds_pooled``),
        which the lane keeps counting into when it sends a bind of the
        job to the pool after all (_bind_cycle), and on the same span the
        seconds the hand-over waited in ``BindLane.submit``
        (``handover_wait_s``)."""
        span = job.span = Trace.open_span()
        if span is not None:
            a = span.args
            a["bind_jobs"] = a.get("bind_jobs", 0) + bool(job.entries)
            a["binds_pooled"] = a.get("binds_pooled", 0) + len(job.pooled)
        if not job.entries:
            job.applied()
            if not job.pooled:
                return
        else:
            if span is None:
                handed = self._bind_lane.submit(job)
            else:
                # the wait for the job before this one (bindlane: the
                # lane holds one at a time); ``submit_s`` contains it
                t_wait = time.perf_counter()
                handed = self._bind_lane.submit(job)
                a["handover_wait_s"] = round(
                    a.get("handover_wait_s", 0.0)
                    + time.perf_counter() - t_wait, 6)
            if not handed:
                # close() raced the serving loop: apply the job here, so
                # the placements still land
                try:
                    self._run_bind_job(job)
                finally:
                    job.applied()
        with self._bind_jobs_lock:
            self._bind_jobs = [j for j in self._bind_jobs if not j.done()]
            self._bind_jobs.append(job)

    def _run_bind_job(self, job: BindJob) -> None:
        """The binder lane's work (thread ``binder-lane``; the serving
        thread only when close() has raced it): the binding cycle of each
        pod of ``job`` in batch order, then ONE settling of what they owe
        the cache's and the histograms' locks.  A bind that raises is
        logged and kept for ``wait_for_inflight_binds``; the rest of the
        job still binds.  Armed, the job leaves ONE span ``bind-job`` on
        its cycle's record, child of the ``commit`` span that handed it
        over: args ``pods``, ``cpu_s`` (this thread's), ``settle_s``,
        ``wake_s`` (hand-over to here), ``pooled`` (binds sent on to the
        pool), ``gc_s`` / ``gc_full``."""
        fold = BindFold(job)
        # armed: the job's own span on its cycle's record (utrace.JobSpan)
        js = (utrace.JobSpan(job.flight, job.span, job.handed_t)
              if job.flight is not None else None)
        pooled0 = len(job.pooled)
        batched = 0
        try:
            for (fwk, binder, hooks_s), run in self._bind_runs(job.entries):
                try:
                    if binder is not None:
                        batched += len(run)
                        self._bind_batch(fwk, binder, hooks_s, run, fold)
                        continue
                    _, qp, state, assumed, node_name, row = run[0]
                    self._bind_cycle(fwk, qp, state, assumed, node_name,
                                     None, job.flight, row, fold)
                except Exception as e:
                    import logging
                    logging.getLogger("kubetpu").exception(
                        "binding cycle of %s/%s%s raised",
                        run[0][1].pod.namespace,
                        run[0][1].pod.metadata.name,
                        " and %d more" % (len(run) - 1)
                        if len(run) > 1 else "")
                    job.error = job.error or e
        finally:
            if js is not None:
                js.settling()
            self._settle_bind_fold(fold)
            if js is not None:
                js.close(pods=len(job.entries), batched=batched,
                         pooled=len(job.pooled) - pooled0)

    @staticmethod
    def _bind_runs(entries):
        """A job's rows cut into maximal runs, in batch order: ``((fwk,
        binder, hooks_s), rows)`` for rows whose binding cycle is their
        Bind alone and whose profile binds a list in one call
        (``_bind_batch`` runs them column-wise; ``hooks_s`` as
        ``Framework.binds_bare`` gives it), ``((fwk, None, None),
        [row])`` for any other row (``_bind_cycle``, as ever).  Read off
        the rows, never a knob."""
        for fwk, rows in itertools.groupby(entries, key=lambda e: e[0]):
            rows = list(rows)
            binder = fwk.batch_binder()
            bare, hooks_s = (fwk.binds_bare([e[1].pod for e in rows])
                             if binder is not None
                             else ([False] * len(rows), None))
            i = 0
            for ok, flags in itertools.groupby(bare):
                n = len(list(flags))
                if ok:
                    yield (fwk, binder, hooks_s), rows[i:i + n]
                else:
                    for e in rows[i:i + n]:
                        yield (fwk, None, None), [e]
                i += n

    def _bind_batch(self, fwk: Framework, binder, hooks_s,
                    rows: List[tuple], fold: BindFold) -> None:
        """The binding cycle of ``rows`` (entries of the lane's job whose
        PreBind, WaitOnPermit and PostBind do nothing) a step at a time
        over all of them, where ``_bind_cycle`` runs all steps a pod:
        ONE Bind call (one store transaction, whose events the cache and
        the queue take as one: _add_all_event_handlers), one stamp of the
        bind table a column, one write of the ``Scheduled`` Events.  What
        a row owes the fold is what ``_bind_cycle_inner`` gives it.  A
        row whose bind was rejected goes on where it would be a pod at a
        time, after the rest: the retry ladder's gate, then the pool for
        its sleeps, or the failure path."""
        flight = fold.job.flight
        done = [e[5] for e in rows]
        if flight is not None:
            flight.stamp_binds(done, utrace.BIND_STARTED)
        rejected: List[tuple] = []
        try:
            bind_start = utrace.wallclock()
            sts = fwk.run_bind_batch(binder, [e[1].pod for e in rows],
                                     [e[4] for e in rows], hooks_s,
                                     sink=fold.points)
            bound = rows
            if not all(st.is_success() for st in sts):
                bound = [e for e, st in zip(rows, sts) if st.is_success()]
                rejected = [(e, st) for e, st in zip(rows, sts)
                            if not st.is_success()]
                done = [e[5] for e in bound]    # theirs: _bind_cycle's
            fold.finished.extend(e[3] for e in bound)
            if self.metrics:
                now = utrace.wallclock()
                fold.bind_s.extend([(now - bind_start,)] * len(bound))
                fold.scheduled.extend(
                    (qp.attempts, now - qp.initial_attempt_timestamp,
                     now - qp.timestamp) for _, qp, *_ in bound)
            if self.recorder:
                self.recorder.events([
                    (qp.pod, "Normal", "Scheduled",
                     f"Successfully assigned {qp.pod.namespace}/"
                     f"{qp.pod.metadata.name} to {node_name}")
                    for _, qp, _, _, node_name, _ in bound])
        finally:
            if flight is not None:
                flight.stamp_binds(done, utrace.BIND_DONE)
        raised = None
        for (_, qp, state, assumed, node_name, row), st in rejected:
            try:
                self._bind_cycle(fwk, qp, state, assumed, node_name, None,
                                 flight, row, fold,
                                 owed=_LadderOwed(st, bind_start))
            except Exception as e:      # the other rejected rows still go on
                raised = raised or e
        if raised is not None:
            raise raised

    def _settle_bind_fold(self, fold: BindFold) -> None:
        """FinishBinding and the bind metrics for everything ``fold``
        collected: the cache's lock and each histogram's once, whatever
        the number of pods."""
        self.cache.finish_binding_many(fold.finished)
        m = self.metrics
        if m is not None:
            m.framework_extension_point_duration.observe_many(fold.points)
            m.binding_duration.observe_many(fold.bind_s)
            m.pods_scheduled(fold.scheduled)

    def _bind_cycle(self, fwk: Framework, qp: QueuedPodInfo, state: CycleState,
                    assumed: api.Pod, node_name: str,
                    binder_override=None, flight=None, row: int = -1,
                    fold: Optional[BindFold] = None,
                    owed: Optional["_LadderOwed"] = None) -> Optional[str]:
        """reference: scheduler.go:628-687.  Called from three threads:
        the binder lane for a pod of a job that cannot ride a batch
        (_run_bind_job; ``fold`` is the job's; ``owed``: its Bind ran in
        a batch and was rejected, the cycle goes on from there), a pool
        thread for a bind that would block the
        lane (_commit), the serving thread itself with
        ``async_binding=False`` or when close() has raced the cycle.
        flight, row: the cycle's CycleRecord and this pod's row of its
        bind table — the bind's start and end are stamped there from
        whichever thread runs it, lock-free (None when disarmed).  The
        lane never sleeps: a bind whose retry ladder owes a sleep leaves
        it here for the pool (_bind_resume), which also stamps its end."""
        if flight is not None:
            flight.stamp_bind(row, utrace.BIND_STARTED)
        on_lane = fold is not None
        if not on_lane:
            fold = BindFold()
        moved = False
        try:
            out = self._bind_cycle_inner(fwk, qp, state, assumed, node_name,
                                         binder_override, fold, owed=owed)
            if not isinstance(out, _LadderOwed):
                return out
            moved = True
            args = (fwk, qp, state, assumed, node_name, flight, row, out)
            try:
                fold.job.pooled.append(
                    self._bind_pool.submit(self._bind_resume, *args))
            except RuntimeError:        # the pool is shut down: see _commit
                return self._bind_resume(*args)
            span = fold.job.span
            if span is not None:
                # counted from the lane: a hand-over of the same phase
                # that races this on the serving thread (the extender
                # path alone) may lose one count, never a bind
                span.args["binds_pooled"] = \
                    span.args.get("binds_pooled", 0) + 1
            return None
        finally:
            if not on_lane:
                self._settle_bind_fold(fold)
            if flight is not None and not moved:
                flight.stamp_bind(row, utrace.BIND_DONE)

    def _bind_resume(self, fwk: Framework, qp: QueuedPodInfo,
                     state: CycleState, assumed: api.Pod, node_name: str,
                     flight, row: int,
                     owed: "_LadderOwed") -> Optional[str]:
        """A pool thread finishes a binding cycle the lane began: the
        retry ladder with its sleeps, then success or failure as ever."""
        fold = BindFold()
        try:
            return self._bind_cycle_inner(fwk, qp, state, assumed,
                                          node_name, None, fold, owed=owed)
        finally:
            self._settle_bind_fold(fold)
            if flight is not None:
                flight.stamp_bind(row, utrace.BIND_DONE)

    def _bound_node(self, pod: api.Pod):
        """The API's current view of a pod's binding: the node name,
        "" when the pod exists unbound, None when the pod is gone (or
        the store is unreadable — the ladder treats unknown as gone and
        stops; the pod's failure path requeues it anyway).  Best-effort:
        a REST mirror that lags just defers the verdict one attempt."""
        try:
            cur = self.store.get_pod(pod.namespace, pod.metadata.name)
        except Exception:
            return None
        return None if cur is None else (cur.spec.node_name or "")

    def _bind_cycle_inner(self, fwk: Framework, qp: QueuedPodInfo,
                          state: CycleState, assumed: api.Pod,
                          node_name: str, binder_override,
                          fold: BindFold,
                          owed: Optional["_LadderOwed"] = None):
        """One pod's binding cycle, the same on every thread.  What it
        owes the cache's and the histograms' locks goes to ``fold`` for
        the caller to settle.  Returns None (bound), the failure's
        message, or -- only on the lane (``fold.job`` set), which never
        sleeps -- a ``_LadderOwed``: the first Bind was rejected and the
        retry ladder owes a sleep; handed back as ``owed``, the cycle
        resumes there."""
        pod = qp.pod

        def failed(st: Status, default: str) -> str:
            self._forget(assumed)
            fwk.run_unreserve_plugins(state, pod, node_name)
            self._record_failure(fwk, qp, st.message())
            return st.message() or default

        if owed is not None:
            st, bind_start = owed
        else:
            st = fwk.wait_on_permit(pod)
            if not st.is_success():
                return failed(st, "permit rejected")
            st = fwk.run_pre_bind_plugins(state, pod, node_name,
                                          sink=fold.points)
            if not st.is_success():
                return failed(st, "prebind failed")
            bind_start = utrace.wallclock()
            if binder_override is not None:
                # extender binding (reference: scheduler.go:457
                # extendersBinding)
                try:
                    binder_override(pod, node_name)
                    st = Status.success()
                except Exception as e:
                    st = Status.error(f"extender bind failed: {e}")
            else:
                st = fwk.run_bind_plugins(state, pod, node_name,
                                          sink=fold.points)
        if binder_override is None and not st.is_success():
            after = self._bind_retry_ladder(fwk, state, pod, node_name, st,
                                            fold)
            if after is None:
                return _LadderOwed(st, bind_start)
            st = after
        if not st.is_success():
            return failed(st, "bind failed")
        fold.finished.append(assumed)
        fwk.run_post_bind_plugins(state, pod, node_name, sink=fold.points)
        if self.metrics:
            now = utrace.wallclock()
            fold.bind_s.append((now - bind_start,))
            fold.scheduled.append((qp.attempts,
                                   now - qp.initial_attempt_timestamp,
                                   now - qp.timestamp))
        if self.recorder:
            self.recorder.event(pod, "Normal", "Scheduled",
                                f"Successfully assigned "
                                f"{pod.namespace}/{pod.metadata.name} to "
                                f"{node_name}")
        return None

    def _bind_retry_ladder(self, fwk: Framework, state: CycleState,
                           pod: api.Pod, node_name: str, st: Status,
                           fold: BindFold) -> Optional[Status]:
        """Transient-bind retry ladder: a bind transport ERROR (socket
        hiccup, injected chaos "bind" fault) retries in place, sleeping
        the pod backoff ladder between attempts
        (pod_initial_backoff_seconds doubling, capped) — the cycle
        already won this placement; a once-flaky API server must not
        cost it.  Each attempt is gated on the API's CURRENT state, never
        on error-message classification: bind is NOT idempotent
        (BindingREST rejects any re-bind, even to the same node), so a
        bind that LANDED with a lost response resolves to success without
        a re-POST, and a pod that is gone or bound elsewhere stops the
        ladder immediately — deterministic failures never sleep it.  Only
        DefaultBinder's exception path ("binding rejected: ...") enters
        at all; config errors fail as before.  Sleeps on the thread that
        ran the bind (a pool thread under async binding, the serving loop
        otherwise) but never on the binder lane: for the lane's fold
        (``fold.job`` set) it returns None where the first sleep is owed,
        having changed nothing, and the caller moves the bind to the
        pool."""
        retries = max(int(getattr(self.config, "bind_retries", 0)), 0)
        delay = min(self.config.pod_initial_backoff_seconds,
                    self.config.pod_max_backoff_seconds)
        attempt = 0
        while (not st.is_success() and attempt < retries
               and st.message().startswith("binding rejected:")):
            bound = self._bound_node(pod)
            if bound == node_name:
                # applied-but-response-lost: already bound right
                st = Status.success()
                attempt += 1     # counts as a recovered attempt
                break
            if bound != "":
                # gone (None) or bound elsewhere: permanent — the
                # normal failure path handles it, no sleeps owed
                break
            if fold.job is not None:
                return None
            attempt += 1
            time.sleep(delay)
            delay = min(delay * 2,
                        self.config.pod_max_backoff_seconds)
            st = fwk.run_bind_plugins(state, pod, node_name,
                                      sink=fold.points)
        if attempt and st.is_success():
            if self.metrics is not None:
                self.metrics.recoveries.inc("bind-retry")
            if self.recorder:
                self.recorder.event(
                    pod, "Normal", "BindRetried",
                    f"bind succeeded after {attempt} retr"
                    f"{'y' if attempt == 1 else 'ies'}")
        return st

    def _forget(self, assumed: api.Pod) -> None:
        # a rolled-back placement invalidates the chained cluster (it may
        # already carry this pod's usage); one locked block so a concurrent
        # _prepare_group can never see the seq bump without the None
        with self._chain_lock:
            self._chain = None
            self._chain_seq += 1
        try:
            self.cache.forget_pod(assumed)
        except ValueError:
            pass

    # ------------------------------------------------------------------ failure

    def _fail(self, fwk: Framework, qp: QueuedPodInfo, state: CycleState,
              node_name: str, message: str,
              preemption_may_help: bool = True,
              cycle=None) -> ScheduleOutcome:
        """reference: scheduler.go:391 recordSchedulingFailure +
        :542-563 — preemption now runs behind the PostFilter extension
        point (framework.go:516; DefaultPreemption)."""
        pod = qp.pod
        nominated = ""
        if preemption_may_help and fwk.post_filter_plugins:
            from .plugins.intree import DefaultPreemption
            if cycle is not None:
                state.write(DefaultPreemption.CYCLE_CONTEXT_KEY, cycle)
            result, st = fwk.run_post_filter_plugins(state, pod)
            if st.is_success() and result is not None:
                nominated = result.nominated_node_name
        self._record_failure(fwk, qp, message, nominated)
        return ScheduleOutcome(pod=pod, node="", err=message,
                               preemption_may_help=preemption_may_help)

    def _record_failure(self, fwk: Framework, qp: QueuedPodInfo,
                        message: str, nominated_node: str = "") -> None:
        pod = qp.pod
        if nominated_node:
            # requeueing re-registers the pod with the nominator from
            # pod.status (queue._add fallback); carry the fresh nomination
            # so it survives (reference: scheduler.go:352 — the API update
            # and queue re-add both see NominatedNodeName)
            pod.status.nominated_node_name = nominated_node
        try:
            # use the cycle captured at pop, not the current counter — pods
            # popped later in the same batch must not mask a move request
            # that raced with this pod's scheduling attempt (reference:
            # scheduler.go:515,559 podSchedulingCycle)
            self.queue.add_unschedulable_if_not_present(
                qp, qp.scheduling_cycle)
        except ValueError:
            pass
        if self.recorder:
            self.recorder.event(pod, "Warning", "FailedScheduling", message)
        try:
            self.store.update_pod_condition(
                pod,
                api.PodCondition(type=api.POD_SCHEDULED, status="False",
                                 reason=api.REASON_UNSCHEDULABLE,
                                 message=message),
                nominated_node_name=nominated_node)
        except Exception:
            pass
        if self.metrics:
            self.metrics.pod_unschedulable()

    # ------------------------------------------------------------------ audit

    def _record_decision(self, pod: api.Pod, outcome: str, **kw) -> None:
        """Fold one pod's (un)scheduling decision into the bounded
        DecisionLog (no-op with KUBETPU_AUDIT=0 — no lock taken)."""
        if not self.decisions.enabled:
            return
        self.decisions.record(PodDecision(
            name=pod.metadata.name, namespace=pod.namespace, uid=pod.uid,
            outcome=outcome, cycle=self.cycle_count, **kw))

    def _audit_failures(self, prep: PreparedCycle, qpods) -> Dict[str, Dict]:
        """Per-plugin attribution for this cycle's failed pods: ONE
        explain_verdicts dispatch + ONE packed [2F+3, B] readback against
        the cycle-start snapshot (models/programs.py).  Like the
        preemption wave's what-if, this is a SECOND device sync on
        cycles that have failures — the retry-churn dedup in
        _commit_group bounds it to cycles whose failed set or committed
        state actually changed.  Returns uid -> PodDecision kwargs; also
        bumps scheduler_framework_rejections_total{plugin} for each pod's
        blocking plugin(s).  Any failure degrades to no attribution — the
        audit must never fail a cycle."""
        try:
            packed = np.asarray(programs.explain_verdicts(
                prep.cluster, prep.batch, prep.cfg, prep.host_ok_dev))
        except Exception:
            import logging
            logging.getLogger("kubetpu").warning(
                "decision audit failed; failures recorded unattributed",
                exc_info=True)
            return {}
        filters = prep.cfg.filters
        F = len(filters)
        counts = packed[:F].tolist()
        blocking = packed[F:2 * F].tolist()
        no_feas = packed[2 * F].tolist()
        best_node = packed[2 * F + 1].tolist()
        best_score = packed[2 * F + 2].tolist()
        node_infos = prep.node_infos
        out: Dict[str, Dict] = {}
        for qp in qpods:
            row = prep.cycle_ctx.row_of.get(qp.pod.uid)
            if row is None:
                continue
            rej = {filters[f]: counts[f][row]
                   for f in range(F) if counts[f][row]}
            blk = [filters[f] for f in range(F) if blocking[f][row]]
            info: Dict[str, object] = {"rejections": rej, "blocking": blk}
            if not no_feas[row] and best_node[row] >= 0:
                # feasible at cycle start — lost to in-batch contention;
                # name the node it would have scored best on
                info["best_node"] = node_infos[best_node[row]].node_name
                info["best_score"] = (best_score[row]
                                      / programs.SCORE_SCALE)
            if self.metrics is not None:
                attributed = blk
                if not attributed and no_feas[row] and rej:
                    # no single filter blocks alone (joint infeasibility):
                    # attribute to the one failing the most nodes
                    attributed = [max(rej, key=rej.get)]
                for plugin in attributed:
                    self.metrics.framework_rejections.inc(plugin)
            out[qp.pod.uid] = info
        return out

    # ------------------------------------------------------------------ loop

    def prewarm(self, ladder_steps: Optional[int] = None) -> bool:
        """Compile the serving program for the CURRENT cluster shape before
        the first pod arrives (VERDICT r3 #7: first-cycle compile was ~6
        cycles of latency).  Builds the real snapshot plus a synthetic
        full-bucket pod batch whose labels are sampled from pods already in
        the cluster (so vocab caps match what real pending pods of the same
        workloads will produce), runs the device program once, and discards
        the result — nothing is assumed, bound or queued.  With the
        persistent XLA cache the compile is loaded, not re-run; cold, it
        happens HERE instead of under the first scheduled pod.
        ladder_steps > 0 additionally dry-runs that many chained cycles so
        the pod-axis bucket ladder a growing cluster will traverse is
        AOT-compiled (see _prewarm_ladder); (bucket, seconds) pairs land
        in self.prewarm_report.  Returns True if a program was warmed.

        AOT-ARTIFACT fast path: when a serve-mode aot runtime is armed
        (KUBETPU_AOT_DIR) and its index carries serving-family rows, the
        build-time serialized executables are deserialize-and-loaded UP
        FRONT, and the dry-run below then dispatches into the resident
        executables — no trace, no lower, no XLA for covered call forms;
        restart cost drops from XLA time to disk-load + one execution.
        The dry-run is NOT skipped: anything the artifact set does not
        cover (a mesh profile's sharded twins, a bucket the set pruned, a
        cfg drift since build) still gets compiled here exactly as if no
        artifacts were armed — arming can never reintroduce the
        first-cycle stall class prewarm exists to prevent."""
        if ladder_steps is None:
            ladder_steps = getattr(self.config, "prewarm_ladder", 0)
        from .utils import aot as _aot
        rt = _aot.active_runtime()
        if rt is not None and rt.mode == "serve":
            self._prewarm_aot(rt)
        fwk = next(iter(self.profiles.values()))
        # a PRIVATE snapshot: the ladder variant runs on a background
        # thread, and mutating the serving loop's self.snapshot from there
        # would race _prepare_group's lock-free node_info_list read
        snap = Snapshot()
        self.cache.update_snapshot(snap)
        node_infos = snap.node_info_list
        if not node_infos:
            return False
        # one synthetic proto per DISTINCT label set sampled from the
        # cluster's pods: the compiled program's shapes include the
        # selector-dedup bucket (U unique selectors), so a single-proto
        # batch (U=1) compiles a DIFFERENT program than a real wave of
        # e.g. 16 app groups (U bucket 32) — prewarm must reproduce the
        # workload's selector diversity or the first real cycle pays the
        # compile anyway
        distinct: Dict[tuple, dict] = {}
        for ni in node_infos:
            if len(distinct) >= 63:
                break
            for pi in ni.pods:
                labels = pi.pod.metadata.labels
                if labels:
                    distinct.setdefault(tuple(sorted(labels.items())),
                                        dict(labels))
                if len(distinct) >= 63:
                    break
        label_sets = list(distinct.values()) or [{}]
        # pad diversity to 31 distinct selector groups: the compiled
        # program keys on the pow2 UNIQUE-selector bucket, and incoming
        # waves are usually more diverse than the possibly-uniform
        # existing pods (e.g. a 16-replica-set wave dedups to bucket 32).
        # Warming the 32-bucket covers 17..32 unique selectors — the
        # common workload shape; rarer diversities still fall back to the
        # persistent cache.
        while len(label_sets) < 31:
            label_sets.append({"kubetpu-prewarm": f"g{len(label_sets)}"})

        def proto_for(idx: int, labels: dict) -> api.Pod:
            p = api.Pod(
                metadata=api.ObjectMeta(name=f"prewarm-{idx}",
                                        namespace="default",
                                        labels=dict(labels)),
                spec=api.PodSpec(containers=[api.Container(
                    name="c", image="",
                    resources=api.ResourceRequirements(
                        requests={"cpu": "1m", "memory": "1Mi"}))]))
            # topology terms make the warmed gang variant
            # intra_batch_topology=True — the serving default; selectors
            # mirror the replica-set pattern (select own labels)
            sel = api.LabelSelector(
                match_labels=dict(labels) or {"kubetpu-prewarm": "x"})
            p.spec.affinity = api.Affinity(
                pod_anti_affinity=api.PodAntiAffinity(
                    required_during_scheduling_ignored_during_execution=[
                        api.PodAffinityTerm(
                            label_selector=sel,
                            topology_key=api.LABEL_HOSTNAME)]))
            # a zone soft-spread makes the warmed active-key set
            # {hostname, zone} — what typical serving batches use
            p.spec.topology_spread_constraints.append(
                api.TopologySpreadConstraint(
                    max_skew=1, topology_key=api.LABEL_ZONE,
                    when_unsatisfiable="ScheduleAnyway",
                    label_selector=sel))
            return p

        protos = [PodInfo(proto_for(i, ls))
                  for i, ls in enumerate(label_sets)]
        B_warm = min(self.config.batch_size, 1024)
        pinfos = [protos[i % len(protos)] for i in range(B_warm)]
        builder = SnapshotBuilder(
            hard_pod_affinity_weight=fwk.hard_pod_affinity_weight)
        builder.intern_pending(protos)
        cluster = builder.build(node_infos).to_device()
        pb = PodBatchBuilder(builder.table)
        batch = self._jax.tree.map(np.asarray, pb.build(pinfos))
        cfg = programs.ProgramConfig(
            filters=fwk.tensor_filters, scores=fwk.tensor_scores,
            hostname_topokey=max(builder.table.topokey.get(api.LABEL_HOSTNAME), 0),
            plugin_args=fwk.tensor_plugin_args(builder.table),
            active_topo_keys=self._batch_topo_keys(builder.table,
                                                   protos[:1]),
            hard_pod_affinity_weight=float(fwk.hard_pod_affinity_weight))
        rng = self._jax.random.PRNGKey(0)
        # profiles with host score plugins serve with a [B, N] bias array;
        # warming the bias=None variant alone would leave the serving
        # shape to compile under the first real cycle
        warm_bias = None
        if fwk.host_score_plugins:
            warm_bias = self._jax.numpy.zeros(
                (batch.valid.shape[0], cluster.allocatable.shape[0]),
                self._jax.numpy.float32)
        # flight-recorder linkage: prewarm gets its OWN cycle record (it
        # runs outside any scheduling cycle) so /debug/flightz and
        # traceview show restart cost — one "prewarm" span per bucket,
        # "aot-load" spans (hit/miss, seconds) nested when the aot seams
        # resolve against a capture runtime
        import contextlib
        fr = utrace.flight_recorder()
        fr_rec = fr.begin_cycle("prewarm") if fr is not None else None
        t0 = time.time()
        with (fr_rec.span("prewarm", mode="dry-run") if fr_rec is not None
              else contextlib.nullcontext()) as sp:
            if self.config.mode == "gang":
                if self._mesh is not None:
                    from .parallel import mesh as pmesh
                    # score_bias=warm_bias like the single-chip branch: mesh
                    # profiles with host score plugins serve the bias-variant
                    # program, so prewarm must compile that variant or the
                    # first real cycle pays the compile stall (ADVICE r5)
                    res = pmesh.sharded_schedule_gang(cluster, batch, cfg,
                                                      rng, self._mesh,
                                                      score_bias=warm_bias)
                else:
                    from .models.gang import run_auction
                    res = run_auction(cluster, batch, cfg, rng,
                                      score_bias=warm_bias)
            elif self._mesh is not None:
                from .parallel import mesh as pmesh
                res = pmesh.sharded_schedule_sequential(
                    cluster, batch, cfg, rng,
                    hard_pod_affinity_weight=float(
                        fwk.hard_pod_affinity_weight),
                    score_bias=warm_bias)
            else:
                res = schedule_sequential(
                    cluster, batch, cfg, rng,
                    hard_pod_affinity_weight=float(
                        fwk.hard_pod_affinity_weight),
                    score_bias=warm_bias)
            np.asarray(res.packed)   # wait out the compile
            if self.decisions.enabled:
                # the decision-audit program dispatches on the first failing
                # cycle; compile it HERE so an unschedulable pod cannot stall
                # the serving loop on the audit's compile (the VERDICT r4 #4
                # stall class prewarm exists to prevent).  BOTH jit variants:
                # host_ok=None and the [B, N] array signature _prepare_group
                # produces whenever host filters / volume masks / nominated
                # pods are in play.  Serving cycles with a different static
                # cfg (active_topo_keys) still fall back to the persistent
                # cache.
                self._prewarm_audit(cluster, batch, cfg)
            if sp is not None:
                sp.args["bucket"] = int(cluster.pod_valid.shape[0])
                sp.args["seconds"] = round(time.time() - t0, 4)
        self.prewarm_report.append(
            (int(cluster.pod_valid.shape[0]), round(time.time() - t0, 2)))
        if ladder_steps and self.config.mode == "gang" \
                and self._mesh is None:
            self._prewarm_ladder(fwk, cluster, batch, cfg, rng, res,
                                 ladder_steps, warm_bias, fr_rec=fr_rec)
        if fr is not None and fr_rec is not None:
            fr.commit_cycle(fr_rec)
        return True

    def _prewarm_aot(self, rt) -> bool:
        """The serialized-artifact half of prewarm: deserialize-and-load
        every serving-family artifact the armed runtime's index carries
        (utils/aot.AotRuntime.preload) so the dry-run that FOLLOWS — and
        the first real cycle — dispatch into resident executables instead
        of tracing.  Returns True when anything loaded (informational;
        the caller runs the dry-run either way, which is what keeps an
        incomplete artifact set from being worse than no artifacts)."""
        import contextlib
        fr = utrace.flight_recorder()
        fr_rec = fr.begin_cycle("prewarm") if fr is not None else None
        t0 = time.time()
        with (fr_rec.span("prewarm", mode="aot-artifact")
              if fr_rec is not None else contextlib.nullcontext()) as sp:
            report = rt.preload()
            if sp is not None:
                sp.args["seconds"] = round(time.time() - t0, 4)
                sp.args["loaded"] = sum(1 for r in report if r["ok"])
        if fr is not None and fr_rec is not None:
            fr_rec.meta["aot"] = rt.stats()
            fr.commit_cycle(fr_rec)
        loaded = [r for r in report if r["ok"]]
        failed = len(report) - len(loaded)
        if failed and self.metrics is not None:
            # corrupt/unreadable artifacts degraded to the per-bucket
            # trace fallback (reasons in the preload report / aot-load
            # flight spans) — count them as recoveries, not silence
            self.metrics.recoveries.inc("aot-fallback", amount=failed)
        for r in loaded:
            self.prewarm_report.append(
                (int(r.get("pod_bucket") or 0), round(r["seconds"], 2)))
        if loaded:
            import logging
            logging.getLogger("kubetpu").info(
                "prewarm: %d aot artifacts loaded in %.2fs (%d failed; "
                "uncovered buckets fall back per dispatch)", len(loaded),
                time.time() - t0, len(report) - len(loaded))
        return bool(loaded)

    def _prewarm_ladder(self, fwk, cluster, batch, cfg, rng, res,
                        steps: int, warm_bias=None, fr_rec=None) -> None:
        """AOT-compile the pow2 bucket ladder a growing chained drain will
        traverse (VERDICT r4 #4: each new bucket stalled serving for tens
        of seconds).  Instead of guessing shapes, this DRY-RUNS the chain
        itself: materialize the synthetic placements with exactly the pad
        buckets _dispatch_group would use, re-run the auction on the grown
        cluster, repeat — every program a real drain of `steps` cycles
        needs is thereby compiled (or loaded from the persistent cache),
        and nothing is committed.  An armed aot runtime PRUNES the ladder:
        buckets the artifact set dropped (the flight recorder never saw
        them serve — tools/kubeaot --prune) are not worth the dry-run
        either."""
        import contextlib

        from .utils import aot as _aot
        from .utils.intern import pow2_bucket
        rt = _aot.active_runtime()
        B_cap = batch.valid.shape[0]
        ta = batch.raa.valid.shape[1]
        for _ in range(steps):
            p_next = int(cluster.pod_valid.shape[0]) + B_cap
            e_next = int(cluster.filter_terms.valid.shape[0]) + B_cap * ta
            if (rt is not None and rt.mode == "serve"
                    and not rt.allows_bucket(pow2_bucket(p_next))):
                # pruned bucket: the recorder's bucket-hit data says no
                # serving cycle ever reached it
                break
            if not self._rung_fits(cluster, pow2_bucket(p_next)):
                # a pod axis the device cannot hold cannot be served
                # either: the ladder ends at the last rung that fits
                break
            t0 = time.time()
            _lsp = (fr_rec.span("prewarm", mode="ladder")
                    if fr_rec is not None else contextlib.nullcontext())
            with _lsp as sp:
                cluster, res = self._prewarm_ladder_step(
                    fwk, cluster, batch, cfg, rng, res, warm_bias,
                    p_next, e_next)
                if sp is not None:
                    sp.args["bucket"] = int(cluster.pod_valid.shape[0])
                    sp.args["seconds"] = round(time.time() - t0, 4)
            self.prewarm_report.append(
                (int(cluster.pod_valid.shape[0]),
                 round(time.time() - t0, 2)))

    def _rung_fits(self, cluster, bucket: int) -> bool:
        """Can the device hold the ladder's next rung beside what is
        resident now?  The rung's cluster, taken as the current one's
        bytes scaled by the pod axis (from shapes: pod_kv [P, L] is
        nearly all of a large cluster), and as much again for what runs
        on it (the delta scatter's densified rows, the auction's [B, P]
        matches).  At 150,000 bound pods the rung after
        262,144 rows is 8.9 GB and the one after that 17.8 GB of a
        16 GB chip.  A backend that reports no limit (the CPU) is held to
        none."""
        stats = self._jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        if not limit:
            return True
        grown = cluster.nbytes * bucket / int(cluster.pod_valid.shape[0])
        return stats.get("bytes_in_use", 0) + 2 * grown <= limit

    def _prewarm_ladder_step(self, fwk, cluster, batch, cfg, rng, res,
                             warm_bias, p_next, e_next):
        """One dry-run rung: materialize the synthetic placements at the
        next pad buckets, re-run the auction (+ audit variants) on the
        grown cluster.  Returns (grown cluster, auction result)."""
        from .models.gang import materialize_assigned, run_auction
        from .utils.intern import pow2_bucket
        cluster = materialize_assigned(
            cluster, batch, res.chosen, res.requested, res.nz,
            res.ports_used, pad_pods_to=pow2_bucket(p_next),
            pad_terms_to=pow2_bucket(e_next), extend_score_terms=True,
            hard_pod_affinity_weight=float(
                fwk.hard_pod_affinity_weight))
        res = run_auction(cluster, batch, cfg, rng,
                          score_bias=warm_bias)
        np.asarray(res.packed)
        if self.decisions.enabled:
            # audit program per pod-axis bucket, like the auction (a
            # drain's failures can land in any grown bucket)
            self._prewarm_audit(cluster, batch, cfg)
        return cluster, res

    def _prewarm_audit(self, cluster, batch, cfg) -> None:
        """Compile both jit variants of the decision-audit program:
        host_ok=None and the [B, N] array signature _prepare_group
        produces whenever host filters / volume masks / nominated pods
        are in play."""
        np.asarray(programs.explain_verdicts(cluster, batch, cfg))
        ones = self._jax.numpy.ones(
            (batch.valid.shape[0], cluster.allocatable.shape[0]), bool)
        np.asarray(programs.explain_verdicts(cluster, batch, cfg,
                                             host_ok=ones))

    def run(self) -> threading.Thread:
        """Start the serving loop (reference: scheduler.go:339 Run)."""
        self.queue.run()
        self.cache.run()
        heap = uheap.HeapPolicy()
        import os
        if (getattr(self.config, "prewarm", True)
                and os.environ.get("KUBETPU_PREWARM", "1") != "0"):
            def prewarm(ladder_steps):
                # a program that cannot compile here cannot compile under
                # the first pod either: the daemon still starts, and the
                # failure is an incident, not a log line
                try:
                    self.prewarm(ladder_steps=ladder_steps)
                except Exception as e:
                    import logging
                    logging.getLogger("kubetpu").warning(
                        "prewarm failed; first cycle pays the compile",
                        exc_info=True)
                    self._record_recovery("prewarm-error", reason=repr(e),
                                          ladder_steps=ladder_steps)
                if ladder_steps:
                    # the ladder's programs are as permanent as the first
                    heap.startup_handoff()

            # current shape blocks startup (it gates the first cycle);
            # the bucket ladder compiles in the background
            prewarm(0)
            steps = getattr(self.config, "prewarm_ladder", 0)
            if steps:
                threading.Thread(target=prewarm, args=(steps,), daemon=True,
                                 name="kubetpu-prewarm-ladder").start()
        # from here to close() the scheduler owns the collector's old
        # generation: the warm cache and the programs compiled so far are
        # handed to the permanent one
        heap.start()
        self._heap = heap

        def loop():
            while not self._stop.is_set():
                t_pass = time.monotonic()
                try:
                    out = self.schedule_pending(timeout=0.2)
                    # the drop is a statement of its own: the cycle's
                    # outcomes die HERE, where the teardown's first
                    # child (``teardown-release``) ends
                    dropped = len(out)
                    del out
                    utrace.teardown_released(dropped)
                    self._longest_pass_s = max(
                        self._longest_pass_s, time.monotonic() - t_pass)
                except Exception:  # the serving loop must never die
                    # (reference: wait.UntilWithContext keeps scheduleOne
                    # running; per-pod errors go through
                    # recordSchedulingFailure, anything else is logged)
                    import logging
                    import traceback
                    logging.getLogger("kubetpu").error(
                        "scheduling cycle panicked:\n%s",
                        traceback.format_exc())
                    time.sleep(0.1)
        t = threading.Thread(target=loop, daemon=True,
                             name="kubetpu-scheduler")
        self._serve_thread = t
        t.start()
        return t

    def wait_for_inflight_binds(self, timeout: float = 10.0) -> None:
        """Return once every bind handed over so far has run, on the lane
        or on the pool; raises TimeoutError past ``timeout``, and what a
        bind raised."""
        deadline = time.time() + timeout
        with self._bind_jobs_lock:
            jobs = list(self._bind_jobs)
        for job in jobs:
            job.result(timeout=max(0.0, deadline - time.time()))
        with self._bind_jobs_lock:
            self._bind_jobs = [j for j in self._bind_jobs if not j.done()]

    # close()'s bound on joining the serving loop, seconds
    CLOSE_JOIN_FLOOR_S = 2.0
    CLOSE_JOIN_CAP_S = 30.0

    def close(self) -> None:
        """Idempotent shutdown: stop the serving loop and JOIN it before
        flushing, so the pipeline flush cannot race a cycle in flight.
        The join is bounded by twice the longest pass the loop has made
        (CLOSE_JOIN_FLOOR_S at the least, CLOSE_JOIN_CAP_S at the most): a
        deployment whose cycles take seconds on the device (a batch under
        a hard spread constraint runs hundreds of auction rounds) is
        given the time its cycle in flight needs, so its binds land
        BEFORE close() returns and not on a caller that has stopped
        watching.  If the loop outlives the bound all the same (a cold
        cycle can be paying a multi-second compile), the in-flight cycle
        is left to that loop and NOT flushed here (its binds it then
        applies itself: _hand_over).  Then drain the binder lane (the
        jobs queued on it are applied, in order, before this returns),
        close the queue (wakes blocked pops, joins flushers), the cache
        (joins cleanup), and the bind pool (binds blocked there finish on
        their own); last, a scheduler that was run() gives the
        collector's permanent generation back (utils/heap.py:
        gc.unfreeze(), unless another scheduler still serves)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        t = self._serve_thread
        serve_loop_live = False
        if (t is not None and t is not threading.current_thread()
                and t.is_alive()):
            t.join(timeout=min(max(self.CLOSE_JOIN_FLOOR_S,
                                   2.0 * self._longest_pass_s),
                               self.CLOSE_JOIN_CAP_S))
            serve_loop_live = t.is_alive()
        self._serve_thread = None
        if not serve_loop_live:
            try:
                self.flush_pipeline()
            except Exception:
                pass
        try:
            self._bind_lane.close()
            self.queue.close()
            self.cache.close()
            self._bind_pool.shutdown(wait=False)
        finally:
            if self._heap is not None:
                self._heap.stop()   # the heap goes back to the process
