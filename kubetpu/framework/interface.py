"""Scheduler Framework plugin contract.

reference: pkg/scheduler/framework/v1alpha1/interface.go — Status codes :77,
MaxNodeScore :85, the 11 extension points (QueueSort, PreFilter(+extensions),
Filter, PreScore, Score(+NormalizeScore), Reserve, Permit, PreBind, Bind,
PostBind, Unreserve) and the Framework/FrameworkHandle contracts :398/:493.

Host plugins implement these Python interfaces 1:1.  Tensorized plugins
additionally declare kernel names consumed by the device program
(kubetpu/models/programs.py) — the framework runner routes them to XLA and
runs only genuinely host-side logic (API writes, volume binding, webhooks)
through these methods.
"""

from __future__ import annotations

import threading
import time
from enum import IntEnum
from typing import Callable, Dict, List, Optional, Set, Tuple

from ..api import types as api

MAX_NODE_SCORE = 100  # reference: interface.go:85
MIN_NODE_SCORE = 0

MAX_TOTAL_PRIORITY = 2 ** 31 - 1


class Code(IntEnum):
    """reference: interface.go:77-103."""
    SUCCESS = 0
    ERROR = 1
    UNSCHEDULABLE = 2
    UNSCHEDULABLE_AND_UNRESOLVABLE = 3
    WAIT = 4
    SKIP = 5


class Status:
    """reference: interface.go:106 Status."""

    __slots__ = ("code", "reasons")

    def __init__(self, code: Code = Code.SUCCESS,
                 reasons: Optional[List[str]] = None):
        self.code = code
        self.reasons = reasons or []

    @classmethod
    def success(cls) -> "Status":
        return cls(Code.SUCCESS)

    @classmethod
    def error(cls, msg: str) -> "Status":
        return cls(Code.ERROR, [msg])

    @classmethod
    def unschedulable(cls, *reasons: str) -> "Status":
        return cls(Code.UNSCHEDULABLE, list(reasons))

    @classmethod
    def unresolvable(cls, *reasons: str) -> "Status":
        return cls(Code.UNSCHEDULABLE_AND_UNRESOLVABLE, list(reasons))

    def is_success(self) -> bool:
        return self.code == Code.SUCCESS

    def is_unschedulable(self) -> bool:
        return self.code in (Code.UNSCHEDULABLE,
                             Code.UNSCHEDULABLE_AND_UNRESOLVABLE)

    def message(self) -> str:
        return ", ".join(self.reasons)

    def __repr__(self) -> str:
        return f"Status({self.code.name}, {self.reasons})"


class FitError(Exception):
    """Scheduling failure carrying per-node reasons
    (reference: core/generic_scheduler.go:68 FitError)."""

    def __init__(self, pod: api.Pod, num_all_nodes: int,
                 filtered_nodes_statuses: Dict[str, Status]):
        self.pod = pod
        self.num_all_nodes = num_all_nodes
        self.filtered_nodes_statuses = filtered_nodes_statuses
        super().__init__(self.error_message())

    def error_message(self) -> str:
        # reference: generic_scheduler.go:82 (ErrorMessageFormat)
        counts: Dict[str, int] = {}
        for st in self.filtered_nodes_statuses.values():
            for r in st.reasons:
                counts[r] = counts.get(r, 0) + 1
        reasons = ", ".join(f"{n} {r}" for r, n in sorted(counts.items()))
        return (f"0/{self.num_all_nodes} nodes are available: {reasons}."
                if reasons else f"0/{self.num_all_nodes} nodes are available.")


class CycleState:
    """Per-scheduling-cycle shared KV store
    (reference: framework/v1alpha1/cycle_state.go:40)."""

    def __init__(self):
        self._data: Dict[str, object] = {}  # kubelint: guarded-by(_lock)
        self._lock = threading.RLock()
        self.record_plugin_metrics = False

    def read(self, key: str):
        with self._lock:
            if key not in self._data:
                raise KeyError(key)
            return self._data[key]

    def write(self, key: str, value: object) -> None:
        with self._lock:
            self._data[key] = value

    def delete(self, key: str) -> None:
        with self._lock:
            self._data.pop(key, None)

    def clone(self) -> "CycleState":
        c = CycleState()
        with self._lock:
            for k, v in self._data.items():
                c._data[k] = v.clone() if hasattr(v, "clone") else v
        c.record_plugin_metrics = self.record_plugin_metrics
        return c


# ---------------------------------------------------------------------------
# plugin interfaces (reference: interface.go:228-396)


class Plugin:
    NAME = "Plugin"

    def name(self) -> str:
        return self.NAME


class QueueSortPlugin(Plugin):
    def less(self, a, b) -> bool:
        raise NotImplementedError

    def sort_key(self, qp) -> tuple:
        """Total-order key equivalent of less(), snapshotted at enqueue time
        (the heap freezes it — see schedqueue/heap.py).  Plugins should
        implement this; the default derives nothing and must be overridden
        when less() is."""
        raise NotImplementedError


class PreFilterPlugin(Plugin):
    def pre_filter(self, state: CycleState, pod: api.Pod) -> Status:
        raise NotImplementedError

    def pre_filter_extensions(self):
        """Returns self if AddPod/RemovePod are implemented, else None
        (reference: interface.go:252 PreFilterExtensions)."""
        return None

    def add_pod(self, state: CycleState, pod_to_schedule: api.Pod,
                pod_to_add: api.Pod, node_info) -> Status:
        return Status.success()

    def remove_pod(self, state: CycleState, pod_to_schedule: api.Pod,
                   pod_to_remove: api.Pod, node_info) -> Status:
        return Status.success()


class FilterPlugin(Plugin):
    def filter(self, state: CycleState, pod: api.Pod, node_info) -> Status:
        raise NotImplementedError


class PostFilterResult:
    """reference: framework/v1alpha1/interface.go:522."""
    __slots__ = ("nominated_node_name",)

    def __init__(self, nominated_node_name: str = ""):
        self.nominated_node_name = nominated_node_name


class PostFilterPlugin(Plugin):
    """Called when no node passed filtering; may make the pod schedulable
    (e.g. by preempting).  Statuses: SUCCESS (made schedulable, result may
    nominate a node), UNSCHEDULABLE (ran fine, couldn't help), anything
    else is an error (reference: framework/v1alpha1/interface.go:278,
    framework.go:516)."""

    def post_filter(self, state: CycleState, pod: api.Pod,
                    filtered_node_status: Dict[str, Status]
                    ) -> Tuple[Optional[PostFilterResult], Status]:
        raise NotImplementedError


class PreScorePlugin(Plugin):
    def pre_score(self, state: CycleState, pod: api.Pod,
                  nodes: List[api.Node]) -> Status:
        raise NotImplementedError


class ScorePlugin(Plugin):
    def score(self, state: CycleState, pod: api.Pod,
              node_name: str) -> Tuple[int, Status]:
        raise NotImplementedError

    def score_extensions(self):
        """Returns self if normalize_score is implemented, else None."""
        return None

    def normalize_score(self, state: CycleState, pod: api.Pod,
                        scores: List[Tuple[str, int]]) -> Tuple[List[Tuple[str, int]], Status]:
        return scores, Status.success()


class ReservePlugin(Plugin):
    def reserve(self, state: CycleState, pod: api.Pod, node_name: str) -> Status:
        raise NotImplementedError


class UnreservePlugin(Plugin):
    def unreserve(self, state: CycleState, pod: api.Pod, node_name: str) -> None:
        raise NotImplementedError


class PermitPlugin(Plugin):
    def permit(self, state: CycleState, pod: api.Pod,
               node_name: str) -> Tuple[Status, float]:
        """Returns (status, timeout_seconds); Wait status parks the pod
        (reference: interface.go:330)."""
        raise NotImplementedError


class PreBindPlugin(Plugin):
    def pre_bind(self, state: CycleState, pod: api.Pod, node_name: str) -> Status:
        raise NotImplementedError


class BindPlugin(Plugin):
    def bind(self, state: CycleState, pod: api.Pod, node_name: str) -> Status:
        """SKIP status passes to the next bind plugin
        (reference: interface.go:376)."""
        raise NotImplementedError


class PostBindPlugin(Plugin):
    def post_bind(self, state: CycleState, pod: api.Pod, node_name: str) -> None:
        raise NotImplementedError


class TensorPlugin(Plugin):
    """A plugin whose Filter/Score semantics are implemented as device
    kernels.  The framework runner collects these into the jitted program's
    ProgramConfig instead of calling per-node Python methods — this is how
    the TPU backend stays 'gated behind the Scheduler Framework plugin
    interface' (BASELINE.json north star)."""
    FILTER_KERNEL: Optional[str] = None   # name in programs.run_filters
    SCORE_KERNEL: Optional[str] = None    # name in programs.run_scores


# ---------------------------------------------------------------------------
# waiting pods (Permit -> Wait)


class WaitingPod:
    """reference: framework/v1alpha1/waiting_pods_map.go:52 waitingPod."""

    def __init__(self, pod: api.Pod, plugin_timeouts: Dict[str, float]):
        self.pod = pod
        self._pending = dict(plugin_timeouts)  # kubelint: guarded-by(_cond)
        self._cond = threading.Condition()
        self._status: Optional[Status] = None
        self._deadline = time.time() + (max(plugin_timeouts.values())
                                        if plugin_timeouts else 0.0)

    def get_pending_plugins(self) -> List[str]:
        with self._cond:
            return list(self._pending)

    def allow(self, plugin_name: str) -> None:
        # reference: waiting_pods_map.go:106
        with self._cond:
            self._pending.pop(plugin_name, None)
            if not self._pending and self._status is None:
                self._status = Status.success()
                self._cond.notify_all()

    def reject(self, msg: str) -> None:
        with self._cond:
            if self._status is None:
                self._status = Status.unschedulable(
                    f"pod {self.pod.metadata.name} rejected while waiting on "
                    f"permit: {msg}")
                self._cond.notify_all()

    def wait(self, timeout: Optional[float] = None) -> Status:
        deadline = self._deadline if timeout is None else time.time() + timeout
        with self._cond:
            while self._status is None:
                remaining = deadline - time.time()
                if remaining <= 0:
                    self._status = Status.unschedulable(
                        "pod rejected due to timeout after waiting on permit")
                    break
                self._cond.wait(timeout=remaining)
            return self._status


class WaitingPodsMap:
    """reference: waiting_pods_map.go:29."""

    def __init__(self):
        self._pods: Dict[str, WaitingPod] = {}  # kubelint: guarded-by(_lock)
        self._lock = threading.RLock()

    def add(self, wp: WaitingPod) -> None:
        with self._lock:
            self._pods[wp.pod.uid] = wp

    def remove(self, uid: str) -> None:
        with self._lock:
            self._pods.pop(uid, None)

    def get(self, uid: str) -> Optional[WaitingPod]:
        with self._lock:
            return self._pods.get(uid)

    def uids(self) -> Set[str]:
        """The pods waiting now, in one locked read."""
        with self._lock:
            return set(self._pods)

    def iterate(self, fn: Callable[[WaitingPod], None]) -> None:
        with self._lock:
            for wp in list(self._pods.values()):
                fn(wp)
