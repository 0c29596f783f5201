"""In-process cluster state store: the framework's apiserver + informer.

Plays the role the API server + client-go informer machinery play for the
reference scheduler (reference: staging/src/k8s.io/client-go/tools/cache
{reflector,delta_fifo,shared_informer}.go; the scheduler's view of it is
addAllEventHandlers, pkg/scheduler/eventhandlers.go:362).  Durable state
lives here (etcd's role); device tensors are disposable projections of it
(SURVEY.md §5 checkpoint/resume).

Writes go through typed methods that fan events out to subscribers
synchronously in-process — the integration-test shape of the reference
(test/integration/util/util.go StartApiserver/StartScheduler), which is how
the parity harness runs without a real control plane.  The `bind` method is
the pods/<name>/binding subresource (reference:
defaultbinder/default_binder.go:56, pkg/registry/core/pod BindingREST).

A write is a TRANSACTION of one or more events (``bind_many``,
``add_many``: many; every other write: one), applied under one hold of
the lock and delivered after it, in this order: first, whole, to each
subscriber that takes a transaction's events as one list
(``subscribe(kind, handler, batched=True)``: the scheduler's own cache
and queue), then event by event, in store order, to each subscriber that
takes them one at a time (``handler(event, old, new)``: a client, a REST
mirror).  So every subscriber sees a transaction's events in store
order, and nobody learns of a bind event by event before the scheduler
has confirmed all of the transaction's binds in its cache: a per-event
subscriber that reacts to a bind (deletes the pod) can no longer get
ahead of the scheduler's own handling of it, as it could within one
fan-out while the order was the order of subscription.
"""

from __future__ import annotations

import copy
import threading
from typing import Callable, Dict, List, Optional, Tuple

from ..api import types as api

Handler = Callable[[str, Optional[object], Optional[object]], None]
# handler(event, old, new) with event in {"add", "update", "delete"}
BatchHandler = Callable[[List[Tuple[str, Optional[object],
                                    Optional[object]]]], None]
# handler([(event, old, new), ...]): one transaction's events, store order

KINDS = ("Pod", "Node", "PersistentVolumeClaim", "PersistentVolume",
         "StorageClass", "CSINode", "Service", "ReplicaSet",
         "ReplicationController", "StatefulSet", "PodDisruptionBudget",
         "Event")


class Conflict(Exception):
    """API write conflict (reference: apierrors.IsConflict paths)."""


class NotFound(Exception):
    pass


class ClusterStore:
    # a write returns once it is applied and its watch events delivered,
    # in the caller's thread: nothing here waits on a network.  The
    # scheduler's bind hand-over reads this (Scheduler._commit); a
    # subclass whose writes leave the process says False
    in_process = True

    def __init__(self):
        self._lock = threading.RLock()
        self._objs: Dict[str, Dict[str, object]] = {k: {} for k in KINDS}  # kubelint: guarded-by(_lock)
        self._subs: Dict[str, List[Handler]] = {k: [] for k in KINDS}  # kubelint: guarded-by(_lock)
        self._batch_subs: Dict[str, List[BatchHandler]] = {k: [] for k in KINDS}  # kubelint: guarded-by(_lock)
        # PV binding assume-cache (reference: scheduler_binder assume cache)
        self._assumed_pv: Dict[str, str] = {}   # pv name -> pvc name  # kubelint: guarded-by(_lock)

    # -- generic ------------------------------------------------------------

    @staticmethod
    def _key(obj) -> str:
        m = obj.metadata
        return f"{m.namespace}/{m.name}" if getattr(obj, "kind", "") in (
            "Pod", "PersistentVolumeClaim", "Service", "ReplicaSet",
            "ReplicationController", "StatefulSet", "PodDisruptionBudget",
            "Event") \
            else m.name

    def subscribe(self, kind: str, handler, batched: bool = False) -> None:
        """``handler(event, old, new)`` an event; with ``batched``,
        ``handler(events)`` a transaction, before the per-event
        subscribers hear of it (module docstring)."""
        with self._lock:
            (self._batch_subs if batched else self._subs)[kind].append(
                handler)
            # replay current state as adds (informer initial List)
            current = list(self._objs[kind].values())
        if batched:
            if current:
                handler([("add", None, obj) for obj in current])
            return
        for obj in current:
            handler("add", None, obj)

    def _subscribers(self, kind: str):
        """Who hears of a write to ``kind``, snapshotted under the
        write's own hold of the lock."""
        return list(self._batch_subs[kind]), list(self._subs[kind])

    @staticmethod
    def _deliver(subs, events) -> None:
        """One transaction's events, after its lock is released: whole
        to the list-taking subscribers first, then event-major to the
        rest (module docstring)."""
        batch_subs, each_subs = subs
        if not events:              # every row of it was refused
            return
        for h in batch_subs:
            h(events)
        for ev in events:
            for h in each_subs:
                h(*ev)

    def add(self, obj) -> None:
        """A transaction of one."""
        (err,) = self._add_transaction((obj,))
        if err is not None:
            raise err

    def add_many(self, objs) -> List[Optional[Exception]]:
        """``add`` each of ``objs`` (one kind) as ONE transaction: one
        hold of the lock, one delivery.  One result an object: None, or
        the Conflict ``add`` would have raised; an object that fails
        does not stop the rest.  A store whose ``add`` is not the one
        above (a remote client's) keeps its say, as in ``bind_many``."""
        if type(self).add is not _ADD:
            return _each(self.add, [(obj,) for obj in objs])
        return self._add_transaction(objs) if objs else []

    def _add_transaction(self, objs) -> List[Optional[Exception]]:
        kind = objs[0].kind
        results: List[Optional[Exception]] = []
        events = []
        with self._lock:
            held = self._objs[kind]
            for obj in objs:
                k = self._key(obj)
                if k in held:
                    results.append(Conflict(f"{kind} {k} already exists"))
                    continue
                obj.metadata.resource_version += 1
                held[k] = obj
                results.append(None)
                events.append(("add", None, obj))
            subs = self._subscribers(kind)
        self._deliver(subs, events)
        return results

    def update(self, obj) -> None:
        kind = obj.kind
        with self._lock:
            k = self._key(obj)
            old = self._objs[kind].get(k)
            if old is None:
                raise NotFound(f"{kind} {k} not found")
            obj.metadata.resource_version = old.metadata.resource_version + 1
            self._objs[kind][k] = obj
            subs = self._subscribers(kind)
        self._deliver(subs, [("update", old, obj)])

    def delete(self, obj) -> None:
        kind = obj.kind
        with self._lock:
            k = self._key(obj)
            old = self._objs[kind].pop(k, None)
            if old is None:
                raise NotFound(f"{kind} {k} not found")
            subs = self._subscribers(kind)
        self._deliver(subs, [("delete", old, None)])

    def get(self, kind: str, key: str):
        with self._lock:
            return self._objs[kind].get(key)

    def list(self, kind: str) -> List[object]:
        with self._lock:
            return list(self._objs[kind].values())

    # -- typed helpers (what plugins/scheduler use) -------------------------

    def get_pod(self, namespace: str, name: str) -> Optional[api.Pod]:
        return self.get("Pod", f"{namespace}/{name}")

    def get_node(self, name: str) -> Optional[api.Node]:
        return self.get("Node", name)

    def get_pvc(self, namespace: str, name: str) -> Optional[api.PersistentVolumeClaim]:
        return self.get("PersistentVolumeClaim", f"{namespace}/{name}")

    def get_pv(self, name: str) -> Optional[api.PersistentVolume]:
        return self.get("PersistentVolume", name)

    def list_pvs(self) -> List[api.PersistentVolume]:
        return self.list("PersistentVolume")

    def get_storage_class(self, name: str) -> Optional[api.StorageClass]:
        return self.get("StorageClass", name)

    def get_csinode(self, name: str) -> Optional[api.CSINode]:
        return self.get("CSINode", name)

    # -- binding subresource ------------------------------------------------

    def bind(self, pod: api.Pod, node_name: str) -> None:
        """POST pods/<name>/binding (reference: default_binder.go:56).
        Fails if the pod is gone or already bound — the scheduler's
        ForgetPod path handles that (scheduler.go:497).  A transaction
        of one."""
        (err,) = self._bind_transaction(((pod, node_name),))
        if err is not None:
            raise err

    def bind_many(self, pairs) -> List[Optional[Exception]]:
        """``bind`` each ``(pod, node_name)`` of ``pairs`` as ONE
        transaction: one hold of the lock applies them in order with
        ``bind``'s own checks a pod, then one delivery of an ``update``
        event a bound pod, in that order.  One result a pair: None, or
        the NotFound / Conflict ``bind`` would have raised; a pair that
        fails does not stop the rest.  A store whose ``bind`` is not
        the one below (a subclass's: a remote client, a test's fault; one
        patched over this class) keeps its say: that ``bind`` runs a
        pair, each a transaction of its own."""
        if type(self).bind is not _BIND:
            return _each(self.bind, pairs)
        return self._bind_transaction(pairs)

    def _bind_transaction(self, pairs) -> List[Optional[Exception]]:
        results: List[Optional[Exception]] = []
        events = []
        with self._lock:
            pods, nodes = self._objs["Pod"], self._objs["Node"]
            for pod, node_name in pairs:
                k = f"{pod.metadata.namespace}/{pod.metadata.name}"
                current: Optional[api.Pod] = pods.get(k)
                if current is None:
                    results.append(NotFound(f"pod {k} not found"))
                elif current.spec.node_name:
                    # reference: pkg/registry/core/pod BindingREST rejects
                    # any re-bind, even to the same node
                    results.append(Conflict(
                        f"pod {k} is already assigned to node "
                        f"{current.spec.node_name}"))
                elif node_name not in nodes:
                    results.append(NotFound(f"node {node_name} not found"))
                else:
                    old = api.shallow_copy(current)
                    old.spec = api.shallow_copy(current.spec)
                    current.spec.node_name = node_name
                    current.status.phase = api.POD_PENDING
                    current.metadata.resource_version += 1
                    results.append(None)
                    events.append(("update", old, current))
            subs = self._subscribers("Pod")
        self._deliver(subs, events)
        return results

    def update_pod_condition(self, pod: api.Pod, condition: api.PodCondition,
                             nominated_node_name: str = "") -> None:
        """Status patch (reference: scheduler.go:739-755 updatePod)."""
        with self._lock:
            k = f"{pod.namespace}/{pod.metadata.name}"
            current: Optional[api.Pod] = self._objs["Pod"].get(k)
            if current is None:
                raise NotFound(f"pod {k} not found")
            old = copy.copy(current)
            conds = [c for c in current.status.conditions
                     if c.type != condition.type]
            conds.append(condition)
            current.status.conditions = conds
            if nominated_node_name:
                current.status.nominated_node_name = nominated_node_name
            current.metadata.resource_version += 1
            subs = self._subscribers("Pod")
        self._deliver(subs, [("update", old, current)])

    # -- PV binding (SchedulerVolumeBinder surface) -------------------------

    def pv_is_bound(self, pv_name: str) -> bool:
        with self._lock:
            if pv_name in self._assumed_pv:
                return True
            for pvc in self._objs["PersistentVolumeClaim"].values():
                if pvc.volume_name == pv_name:
                    return True
            return False

    def assume_pv_binding(self, pv_name: str, pvc_name: str) -> None:
        with self._lock:
            self._assumed_pv[pv_name] = pvc_name

    def forget_pv_binding(self, pv_name: str) -> None:
        with self._lock:
            self._assumed_pv.pop(pv_name, None)

    def bind_pvc(self, namespace: str, pvc_name: str, pv_name: str,
                 node_name: str) -> None:
        """Write the binding through the 'API' (reference:
        scheduler_binder.go BindPodVolumes -> PVC/PV updates).  Emits a
        PVC update event so watchers (and REST mirrors) see the
        binding."""
        with self._lock:
            pvc = self._objs["PersistentVolumeClaim"].get(f"{namespace}/{pvc_name}")
            if pvc is None:
                raise NotFound(f"pvc {namespace}/{pvc_name} not found")
            old = copy.copy(pvc)
            old.metadata = copy.copy(pvc.metadata)
            if pv_name:
                pvc.volume_name = pv_name
                self._assumed_pv.pop(pv_name, None)
                pvc.phase = "Bound"
            else:
                # delayed provisioning: stamp the selected node and leave the
                # claim Pending for the (external) provisioner (reference:
                # volume.kubernetes.io/selected-node annotation)
                pvc.metadata.annotations = dict(pvc.metadata.annotations)
                pvc.metadata.annotations[
                    "volume.kubernetes.io/selected-node"] = node_name
            pvc.metadata.resource_version += 1
            subs = self._subscribers("PersistentVolumeClaim")
        self._deliver(subs, [("update", old, pvc)])

    # -- spread selectors (DefaultPodTopologySpread) ------------------------

    def default_spread_selector(self, pod: api.Pod):
        """Combined Service/RC/RS/SS selector for the pod (reference:
        defaultpodtopologyspread helpers, plugins/helper/spread.go
        DefaultSelector).  Returns an api.LabelSelector or None."""
        reqs: List[api.LabelSelectorRequirement] = []
        with self._lock:
            for svc in self._objs["Service"].values():
                if svc.metadata.namespace != pod.namespace or not svc.selector:
                    continue
                if all(pod.metadata.labels.get(k) == v
                       for k, v in svc.selector.items()):
                    reqs.extend(api.LabelSelectorRequirement(k, "In", [v])
                                for k, v in svc.selector.items())
            for rc in self._objs["ReplicationController"].values():
                if rc.metadata.namespace != pod.namespace or not rc.selector:
                    continue
                if all(pod.metadata.labels.get(k) == v
                       for k, v in rc.selector.items()):
                    reqs.extend(api.LabelSelectorRequirement(k, "In", [v])
                                for k, v in rc.selector.items())
            for kind in ("ReplicaSet", "StatefulSet"):
                for rs in self._objs[kind].values():
                    if rs.metadata.namespace != pod.namespace:
                        continue
                    if rs.selector is not None and not rs.selector.is_empty() \
                            and rs.selector.matches(pod.metadata.labels):
                        reqs.extend(rs.selector.requirements())
        if not reqs:
            return None
        return api.LabelSelector(match_expressions=reqs)


# the single writes as written above: what the batch forms may stand in for
_ADD, _BIND = ClusterStore.add, ClusterStore.bind


def _each(write, rows) -> List[Optional[Exception]]:
    """``write(*row)`` a row, each a transaction of its own; one result a
    row: None, or what it raised."""
    results: List[Optional[Exception]] = []
    for row in rows:
        try:
            write(*row)
        except Exception as e:  # noqa: BLE001 — a result, not a raise
            results.append(e)
        else:
            results.append(None)
    return results
