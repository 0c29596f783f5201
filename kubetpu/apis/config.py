"""Scheduler component configuration objects.

reference: pkg/scheduler/apis/config/types.go — KubeSchedulerConfiguration
:55, KubeSchedulerProfile :115, Plugins :176, PluginSet :217, Plugin :230,
DefaultPercentageOfNodesToScore :251.  YAML decoding/defaulting lives in
kubetpu/apis/load.py; these are the internal (typed) forms.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

DEFAULT_PERCENTAGE_OF_NODES_TO_SCORE = 0  # 0 => adaptive (types.go:251)
DEFAULT_SCHEDULER_NAME = "default-scheduler"

EXTENSION_POINTS = (
    "queue_sort", "pre_filter", "filter", "post_filter", "pre_score",
    "score", "reserve", "permit", "pre_bind", "bind", "post_bind",
    "unreserve",
)


@dataclass
class Plugin:
    """reference: types.go:230 (Plugin — name + weight)."""
    name: str
    weight: int = 0


@dataclass
class PluginSet:
    """reference: types.go:217."""
    enabled: List[Plugin] = field(default_factory=list)
    disabled: List[Plugin] = field(default_factory=list)


@dataclass
class Plugins:
    """One PluginSet per extension point (reference: types.go:176)."""
    queue_sort: PluginSet = field(default_factory=PluginSet)
    pre_filter: PluginSet = field(default_factory=PluginSet)
    filter: PluginSet = field(default_factory=PluginSet)
    post_filter: PluginSet = field(default_factory=PluginSet)
    pre_score: PluginSet = field(default_factory=PluginSet)
    score: PluginSet = field(default_factory=PluginSet)
    reserve: PluginSet = field(default_factory=PluginSet)
    permit: PluginSet = field(default_factory=PluginSet)
    pre_bind: PluginSet = field(default_factory=PluginSet)
    bind: PluginSet = field(default_factory=PluginSet)
    post_bind: PluginSet = field(default_factory=PluginSet)
    unreserve: PluginSet = field(default_factory=PluginSet)

    def apply(self, custom: Optional["Plugins"]) -> "Plugins":
        """Merge a profile's custom plugins over these defaults
        (reference: types.go:195 Plugins.Apply / mergePluginSets)."""
        if custom is None:
            return self
        out = Plugins()
        for ep in EXTENSION_POINTS:
            default: PluginSet = getattr(self, ep)
            override: PluginSet = getattr(custom, ep)
            disabled = {p.name for p in override.disabled}
            star = "*" in disabled
            enabled = [p for p in default.enabled
                       if not star and p.name not in disabled]
            enabled += list(override.enabled)
            setattr(out, ep, PluginSet(enabled=enabled))
        return out


@dataclass
class KubeSchedulerProfile:
    """reference: types.go:115."""
    scheduler_name: str = DEFAULT_SCHEDULER_NAME
    plugins: Optional[Plugins] = None
    plugin_config: Dict[str, Any] = field(default_factory=dict)


@dataclass
class KubeSchedulerConfiguration:
    """reference: types.go:55."""
    profiles: List[KubeSchedulerProfile] = field(default_factory=list)
    # scheduling behavior
    percentage_of_nodes_to_score: int = DEFAULT_PERCENTAGE_OF_NODES_TO_SCORE
    pod_initial_backoff_seconds: float = 1.0     # types.go:97
    pod_max_backoff_seconds: float = 10.0        # types.go:103
    # HA / serving
    leader_election: bool = False
    metrics_bind_address: str = ""
    health_bind_address: str = ""
    enable_profiling: bool = True                # types.go:76
    enable_contention_profiling: bool = True
    disable_preemption: bool = False             # types.go:85
    # extenders (reference: types.go:72 Extenders)
    extenders: List[Any] = field(default_factory=list)
    # TPU extensions
    batch_size: int = 256        # device batch (B axis); 1 = exact replay
    # "sequential": the lax.scan replay preserving the reference's serial
    # scheduleOne semantics exactly (scheduler.go:509).  "gang": the
    # conflict-free auction (models/gang.py) — O(rounds) parallel passes,
    # exact capacity/hostPort semantics, topology scored against the
    # snapshot rather than intra-batch placements.
    mode: str = "sequential"
    # Deadline-guarded dispatch (the self-healing runtime): a cycle whose
    # device dispatch errors — or whose dispatch-to-readback wall time
    # exceeds this deadline — is DISCARDED before anything commits: the
    # AOT runtime, if armed, is disarmed (AOT artifacts -> trace) with
    # a recorded reason, the device residents are invalidated (next
    # cycle resyncs from the host mirror), and the cycle's pods are
    # requeued through the backoff queue — never lost,
    # never double-bound.  0 (default) disables the deadline; dispatch
    # ERRORS are always recovered.  Env override: KUBETPU_DISPATCH_DEADLINE.
    dispatch_deadline_seconds: float = 0.0
    # Transient bind failures (DefaultBinder's transport-exception path)
    # retry this many times before the pod is marked failed, sleeping the
    # pod backoff ladder between attempts (pod_initial_backoff_seconds
    # doubling, capped at pod_max_backoff_seconds) — a once-flaky API
    # server must not cost a placement the cycle already won.  Each retry
    # first checks whether the bind landed server-side (bind is not
    # idempotent; a lost response must not re-POST into a Conflict).
    # Retries sleep on a binder pool thread under async binding (the
    # default; never on the binder lane, which hands such a bind to the
    # pool), on the serving loop under sync binding — where each failing
    # pod can stall it for the summed backoff.
    bind_retries: int = 2
    mesh_shape: Optional[tuple] = None
    # Cycle chaining (gang mode): reuse the auction's materialized cluster
    # as the next cycle's snapshot tensors instead of re-tensorizing
    # (SURVEY §7 delta updates).  Default ON as of round 4: a randomized
    # chain-vs-fresh-rebuild equivalence test under event churn
    # (tests/test_chain.py) proves placements identical, and the measured
    # multi-cycle drain (round 4, CPU) showed ~7% e2e at 4096x1000
    # — growing with cluster size, since the saved SnapshotBuilder.build
    # scales with nodes+pods while the chain update is O(batch).  Any
    # store event the chain cannot account for still forces a full
    # rebuild (event-sequence invalidation, scheduler.py).
    chain_cycles: bool = True
    # compile the serving program for the current cluster shape at startup
    # (Scheduler.run), before the first pod arrives — with the persistent
    # XLA cache this is a cache load; cold, it moves the first-cycle
    # compile out of the serving path (VERDICT r3 #7)
    prewarm: bool = True
    # prewarm_ladder > 0 additionally AOT-compiles the pod-axis pow2
    # bucket ladder a growing chained drain will traverse, by dry-running
    # that many chained cycles in a BACKGROUND thread after startup (gang
    # mode; see Scheduler._prewarm_ladder).  Without it, each new bucket
    # a drain grows into stalls serving for its compile.  Measured warm
    # restart (round 5, CPU; 1024-pod wave x 1000 nodes):
    # first cycle 0.36 s.
    prewarm_ladder: int = 2
    # Pipelined drain (gang + chain_cycles only): schedule_pending
    # dispatches cycle k against the previous cycle's speculative on-device
    # chained cluster BEFORE committing older cycles, so cycle k's device
    # execution overlaps both the commit loop of k-1 and the tensorize of
    # k+1 (SURVEY §7 "batched, donated, overlapped"; the reference's
    # analog is the bind goroutine, scheduler.go:628).  Outcomes therefore
    # LAG up to pipeline_depth-1 cycles: each schedule_pending call
    # returns previously dispatched cycles' outcomes, and final calls
    # with an empty queue flush the in-flight ring one cycle per call.
    # A commit failure or an unaccounted store event discards the
    # speculative dispatches and re-runs those cycles against a rebuilt
    # snapshot; batches needing host filter masks (volume pods)
    # serialize on the in-flight commits, so placements match the
    # synchronous drain.  Known bounded lag: the nominated-pods overlay
    # sees preemption nominations from an in-flight cycle only once it
    # commits (nominations only shrink retry feasibility, never
    # correctness of committed placements).
    pipeline_cycles: bool = False
    # Depth of the pipelined executor's in-flight ring (kubetpu/
    # pipeline.py): the maximum number of cycles in flight at once —
    # prepare(k+1) overlaps device(k) and commit/bind(k-1).  1 = fully
    # synchronous (every cycle commits before the next pops), 2 = the
    # historical double-buffered chain (the default), higher depths park
    # more dispatched-but-uncommitted cycles between schedule_pending
    # calls.  Placements are bit-identical at every depth
    # (tests/test_pipeline.py).  Env override:
    # KUBETPU_PIPELINE_DEPTH (an operator can re-depth a live fleet).
    pipeline_depth: int = 2

    def profile_for(self, name: str) -> Optional[KubeSchedulerProfile]:
        for p in self.profiles:
            if p.scheduler_name == name:
                return p
        return None
