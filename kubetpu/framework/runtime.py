"""Framework runner: the concrete plugin pipeline.

reference: pkg/scheduler/framework/v1alpha1/framework.go (NewFramework :205,
RunPreFilterPlugins :369, RunFilterPlugins :477, RunPreScorePlugins :543,
RunScorePlugins :579, RunReservePlugins, RunPermitPlugins :818,
RunBindPlugins :708, WaitOnPermit).

The TPU twist: enabled plugins are partitioned into *tensorized* plugins
(device kernels, collected into a ProgramConfig and executed for the whole
pod batch in one XLA program) and *host* plugins (Python methods, run only
when `relevant(pod)` — volumes, out-of-tree extensions).  The extension
points below therefore run ONLY host plugins; the tensor side's results
arrive as dense masks/scores from kubetpu/models/programs.py.  That keeps
the device fast path pure while preserving the reference's plugin contract
for everything else.

The contract of ``relevant(pod)``: it may read only the pod's namespace,
labels, annotations, owner references and ``spec`` (and the plugin's own
arguments) -- never its name, uid, resource version, timestamps or status,
and nothing of the cluster.  Those fields are the key of a pod CLASS
(framework/types.py ``classify_pods``): the scheduler asks a plugin once a
class of a cycle's pods whether it cares and takes the answer for every pod
of the class.  The extension points themselves (``pre_filter``, ``filter``,
``reserve``, ``permit``...) still run once a pod, with the pod's own
CycleState.
"""

from __future__ import annotations

import functools
import threading
import time
from typing import Dict, List, Optional, Tuple

from ..api import types as api
from ..apis.config import KubeSchedulerProfile, Plugins
from . import interface as fw
from .interface import Code, CycleState, Status, TensorPlugin, WaitingPod, WaitingPodsMap
from .provider import default_plugins

MAX_PERMIT_TIMEOUT = 600.0  # reference: interface.go maxTimeout 15min; we cap lower


def _status_label(result) -> str:
    """Status label for the extension-point histogram (reference:
    framework.go frameworkMetric status values)."""
    st = result[1] if isinstance(result, tuple) else result
    if st is None or st.is_success():
        return "Success"
    if st.code == Code.WAIT:
        return "Wait"
    return "Unschedulable" if st.is_unschedulable() else "Error"


def _timed_point(point: str):
    """Observe scheduler_framework_extension_point_duration_seconds for
    one host extension point (reference: framework.go:369,660,678,708,
    818 each wrap their run in metrics.ObserveExtensionPoint).  Only the
    per-pod-per-cycle points are instrumented — the per-(pod, node)
    Filter loop is deliberately unsampled (see utils/metrics.py note).
    Without a metrics registry the wrapper is one attribute read.
    sink: a caller that runs many pods in a row (the scheduler's binder
    lane) passes a list; the observation lands there as a ``(seconds,
    point, status)`` row for ONE ``Histogram.observe_many`` once the run
    is over, and the histogram's lock is not taken here."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(self, *args, sink=None, **kwargs):
            m = self.metrics
            if m is None:
                return fn(self, *args, **kwargs)
            t0 = time.time()
            result = fn(self, *args, **kwargs)
            if sink is None:
                m.framework_extension_point_duration.observe(
                    time.time() - t0, point, _status_label(result))
            else:
                sink.append((time.time() - t0, point,
                             _status_label(result)))
            return result
        return wrapper
    return deco


class Framework:
    """One framework per profile (reference: framework.go:96 framework)."""

    def __init__(self, registry, profile: Optional[KubeSchedulerProfile] = None,
                 base_plugins: Optional[Plugins] = None, client=None,
                 nominator=None, metrics=None):
        self.client = client
        self.nominator = nominator
        self.metrics = metrics
        self.profile_name = profile.scheduler_name if profile else "default-scheduler"
        plugins = (base_plugins or default_plugins()).apply(
            profile.plugins if profile else None)
        self.plugins_config = plugins
        args = dict(profile.plugin_config) if profile else {}

        self._instances: Dict[str, fw.Plugin] = {}

        def instantiate(name: str) -> fw.Plugin:
            if name not in self._instances:
                factory = registry.get(name)
                if factory is None:
                    raise ValueError(f"plugin {name} not in registry")
                self._instances[name] = factory(args.get(name), self)
            return self._instances[name]

        def point(ps, iface) -> List[fw.Plugin]:
            out = []
            for p in ps.enabled:
                inst = instantiate(p.name)
                if not isinstance(inst, iface):
                    raise ValueError(
                        f"plugin {p.name} does not implement {iface.__name__}")
                out.append(inst)
            return out

        self.queue_sort_plugins = point(plugins.queue_sort, fw.QueueSortPlugin)
        self.pre_filter_plugins = point(plugins.pre_filter, fw.PreFilterPlugin)
        self.filter_plugins = point(plugins.filter, fw.FilterPlugin)
        self.post_filter_plugins = point(plugins.post_filter,
                                         fw.PostFilterPlugin)
        self.pre_score_plugins = point(plugins.pre_score, fw.PreScorePlugin)
        self.score_plugins = point(plugins.score, fw.ScorePlugin)
        self.score_weights = {p.name: p.weight or 1
                              for p in plugins.score.enabled}
        self.reserve_plugins = point(plugins.reserve, fw.ReservePlugin)
        self.permit_plugins = point(plugins.permit, fw.PermitPlugin)
        self.pre_bind_plugins = point(plugins.pre_bind, fw.PreBindPlugin)
        self.bind_plugins = point(plugins.bind, fw.BindPlugin)
        self.post_bind_plugins = point(plugins.post_bind, fw.PostBindPlugin)
        self.unreserve_plugins = point(plugins.unreserve, fw.UnreservePlugin)
        self.waiting_pods = WaitingPodsMap()

        # -- tensor/host partition ------------------------------------------
        self.tensor_filters: Tuple[str, ...] = tuple(
            p.FILTER_KERNEL for p in self.filter_plugins
            if isinstance(p, TensorPlugin) and p.FILTER_KERNEL)
        self.tensor_scores: Tuple[Tuple[str, int], ...] = tuple(
            (p.SCORE_KERNEL, self.score_weights[p.name()])
            for p in self.score_plugins
            if isinstance(p, TensorPlugin) and p.SCORE_KERNEL)
        self.host_filter_plugins = [
            p for p in self.filter_plugins
            if not (isinstance(p, TensorPlugin) and p.FILTER_KERNEL)]
        self.host_score_plugins = [
            p for p in self.score_plugins
            if not (isinstance(p, TensorPlugin) and p.SCORE_KERNEL)]
        self.host_pre_filter_plugins = [
            p for p in self.pre_filter_plugins
            if not isinstance(p, TensorPlugin)]
        self.host_pre_score_plugins = [
            p for p in self.pre_score_plugins
            if not isinstance(p, TensorPlugin)]
        ipa = self._instances.get("InterPodAffinity")
        self.hard_pod_affinity_weight = getattr(
            ipa, "hard_pod_affinity_weight", 1)

    def tensor_plugin_args(self, table) -> Tuple[Tuple[str, Tuple], ...]:
        """Resolve per-plugin static kernel args against the intern table
        (e.g. NodeLabel key ids, RequestedToCapacityRatio shape)."""
        out = []
        for name, inst in self._instances.items():
            ka = getattr(inst, "kernel_args", None)
            if ka is not None and isinstance(inst, TensorPlugin):
                out.append((name, ka(table)))
        return tuple(out)

    def binds_in_process(self) -> bool:
        """Whether a bind through this profile's Bind plugins stays in
        this process: every one of them writes through a client that
        says so of itself (``ClusterStore.in_process``).  A plugin that
        names no client is not known to, and counts as remote."""
        return bool(self.bind_plugins) and all(
            getattr(getattr(p, "client", None), "in_process", False)
            for p in self.bind_plugins)

    def queue_sort_less(self, a, b) -> bool:
        # reference: framework.go:358 QueueSortFunc (exactly one plugin)
        return self.queue_sort_plugins[0].less(a, b)

    def queue_sort_key(self, qp) -> tuple:
        return self.queue_sort_plugins[0].sort_key(qp)

    @staticmethod
    def _relevant(plugin, pod) -> bool:
        rel = getattr(plugin, "relevant", None)
        return rel(pod) if rel is not None else True

    @classmethod
    def relevant_plugins(cls, plugins, pod: api.Pod) -> List[fw.Plugin]:
        """Those of ``plugins`` that are ``relevant`` to the pod -- and to
        every pod of its class (module docstring), so a caller with many
        pods of one class asks once."""
        return [p for p in plugins if cls._relevant(p, pod)]

    # -- extension points (host plugins only; see module docstring) ---------

    @_timed_point("PreFilter")
    def run_pre_filter_plugins(self, state: CycleState, pod: api.Pod,
                               relevant: Optional[List[fw.Plugin]] = None
                               ) -> Status:
        """reference: framework.go:369.  relevant: the pod's class's
        ``relevant_plugins`` of the host PreFilter plugins, where the
        caller holds them."""
        if relevant is None:
            relevant = self.relevant_plugins(self.host_pre_filter_plugins,
                                             pod)
        for p in relevant:
            st = p.pre_filter(state, pod)
            if not st.is_success():
                if st.is_unschedulable():
                    return st
                return Status.error(
                    f'error while running "{p.name()}" prefilter plugin for '
                    f'pod "{pod.metadata.name}": {st.message()}')
        return Status.success()

    def run_filter_plugins(self, state: CycleState, pod: api.Pod,
                           node_info) -> Status:
        """Host filters for one node (reference: framework.go:477); the
        tensor filters already produced the dense feasibility mask."""
        for p in self.host_filter_plugins:
            if not self._relevant(p, pod):
                continue
            st = p.filter(state, pod, node_info)
            if not st.is_success():
                if not st.is_unschedulable():
                    return Status.error(st.message() or p.name())
                if not st.reasons:
                    st.reasons = [f"filter plugin {p.name()} failed"]
                return st
        return Status.success()

    def has_relevant_host_filters(self, pod: api.Pod,
                                  exclude=frozenset()) -> bool:
        """exclude: plugin names whose verdicts something else already
        covers (the scheduler's device-side volume mask passes the covered
        set so fully-covered pods skip the per-node Python filter loop)."""
        return any(self._relevant(p, pod) for p in self.host_filter_plugins
                   if p.name() not in exclude)

    def run_pre_score_plugins(self, state: CycleState, pod: api.Pod,
                              nodes: List[api.Node]) -> Status:
        for p in self.host_pre_score_plugins:
            if not self._relevant(p, pod):
                continue
            st = p.pre_score(state, pod, nodes)
            if not st.is_success():
                return Status.error(
                    f'error while running "{p.name()}" prescore plugin: '
                    f'{st.message()}')
        return Status.success()

    def run_host_score_plugins(self, state: CycleState, pod: api.Pod,
                               node_names: List[str]) -> Dict[str, List[int]]:
        """Host scores per node (reference: framework.go:579 RunScorePlugins
        with NormalizeScore :613 and weights :633).  Returns weighted
        per-plugin score lists aligned with node_names."""
        out: Dict[str, List[int]] = {}
        for p in self.host_score_plugins:
            if not self._relevant(p, pod):
                continue
            scores = []
            for name in node_names:
                s, st = p.score(state, pod, name)
                if not st.is_success():
                    raise RuntimeError(
                        f"score plugin {p.name()}: {st.message()}")
                scores.append((name, s))
            ext = p.score_extensions()
            if ext is not None:
                scores, st = ext.normalize_score(state, pod, scores)
                if not st.is_success():
                    raise RuntimeError(
                        f"normalize {p.name()}: {st.message()}")
            w = self.score_weights.get(p.name(), 1)
            out[p.name()] = [s * w for _, s in scores]
        return out

    @_timed_point("Reserve")
    def run_reserve_plugins(self, state: CycleState, pod: api.Pod,
                            node_name: str) -> Status:
        # reference: framework.go:660
        for p in self.reserve_plugins:
            if not self._relevant(p, pod):
                continue
            st = p.reserve(state, pod, node_name)
            if not st.is_success():
                return Status.error(
                    f'error while running "{p.name()}" reserve plugin: '
                    f'{st.message()}')
        return Status.success()

    def run_unreserve_plugins(self, state: CycleState, pod: api.Pod,
                              node_name: str) -> None:
        for p in self.unreserve_plugins:
            if self._relevant(p, pod):
                p.unreserve(state, pod, node_name)

    @_timed_point("Permit")
    def run_permit_plugins(self, state: CycleState, pod: api.Pod,
                           node_name: str) -> Status:
        """reference: framework.go:818 — collects Wait verdicts into a
        WaitingPod with per-plugin timeouts."""
        plugin_timeouts: Dict[str, float] = {}
        status_code = Code.SUCCESS
        for p in self.permit_plugins:
            if not self._relevant(p, pod):
                continue
            st, timeout = p.permit(state, pod, node_name)
            if st.is_success():
                continue
            if st.is_unschedulable():
                return st
            if st.code == Code.WAIT:
                plugin_timeouts[p.name()] = min(timeout, MAX_PERMIT_TIMEOUT)
                status_code = Code.WAIT
            else:
                return Status.error(
                    f'error while running "{p.name()}" permit plugin: '
                    f'{st.message()}')
        if status_code == Code.WAIT:
            wp = WaitingPod(pod, plugin_timeouts)
            self.waiting_pods.add(wp)
            return Status(Code.WAIT)
        return Status.success()

    def wait_on_permit(self, pod: api.Pod) -> Status:
        # reference: framework.go:775 WaitOnPermit — the permit-wait
        # histogram is observed only for pods that actually entered a
        # Wait (result: allowed/rejected, matching the reference labels)
        wp = self.waiting_pods.get(pod.uid)
        if wp is None:
            return Status.success()
        t0 = time.time()
        try:
            st = wp.wait()
        finally:
            self.waiting_pods.remove(pod.uid)
        if self.metrics is not None:
            self.metrics.permit_wait_duration.observe(
                time.time() - t0,
                "allowed" if st.is_success() else "rejected")
        return st

    @_timed_point("PreBind")
    def run_pre_bind_plugins(self, state: CycleState, pod: api.Pod,
                             node_name: str) -> Status:
        # reference: framework.go:678
        for p in self.pre_bind_plugins:
            if not self._relevant(p, pod):
                continue
            st = p.pre_bind(state, pod, node_name)
            if not st.is_success():
                return Status.error(
                    f'error while running "{p.name()}" prebind plugin: '
                    f'{st.message()}')
        return Status.success()

    @_timed_point("PostFilter")
    def run_post_filter_plugins(self, state: CycleState, pod: api.Pod,
                                filtered_node_status=None):
        """reference: framework.go:514 RunPostFilterPlugins — run until the
        first SUCCESS or error; UNSCHEDULABLE statuses accumulate.  Returns
        (PostFilterResult or None, Status)."""
        reasons: List[str] = []
        for p in self.post_filter_plugins:
            r, st = p.post_filter(state, pod, filtered_node_status or {})
            if st.is_success():
                return r, st
            if not st.is_unschedulable():
                return None, Status.error(
                    f'error while running "{p.name()}" postfilter plugin: '
                    f'{st.message()}')
            reasons.extend(st.reasons)
        return None, Status(Code.UNSCHEDULABLE, reasons)

    @_timed_point("Bind")
    def run_bind_plugins(self, state: CycleState, pod: api.Pod,
                         node_name: str) -> Status:
        # reference: framework.go:708 — SKIP falls through to the next binder
        if not self.bind_plugins:
            return Status.error("no bind plugin configured")
        for p in self.bind_plugins:
            st = p.bind(state, pod, node_name)
            if st.code == Code.SKIP:
                continue
            return st
        return Status(Code.SKIP, [
            f"all bind plugins skipped binding pod "
            f"{pod.namespace}/{pod.metadata.name}"])

    @_timed_point("PostBind")
    def run_post_bind_plugins(self, state: CycleState, pod: api.Pod,
                              node_name: str) -> None:
        for p in self.post_bind_plugins:
            if self._relevant(p, pod):
                p.post_bind(state, pod, node_name)

    # -- the commit and the binding cycle of many pods at once ---------------

    def commits_bare(self, pods: List[api.Pod]
                     ) -> Tuple[List[bool], Tuple[float, float]]:
        """Per pod: would Reserve, Unreserve and Permit do nothing for
        it?  (No plugin of the three is ``relevant`` to it, so it cannot
        come back from Permit with WAIT either.)  Such a pod's commit is
        its assume alone, which ``Scheduler._commit_run`` does for many.
        ``relevant`` holds for a pod's whole class (module docstring):
        the caller asks of one pod a class.  Beside the flags, the
        seconds the walk of Reserve's (with Unreserve's) and of Permit's
        plugins took in all: all those points do for such pods, so what
        the caller observes for them, shared out."""
        t0 = time.time()
        res = self._any_relevant(self.reserve_plugins, pods)
        unres = self._any_relevant(self.unreserve_plugins, pods)
        t1 = time.time()
        permit = self._any_relevant(self.permit_plugins, pods)
        t2 = time.time()
        return ([not (a or b or c) for a, b, c in zip(res, unres, permit)],
                (t1 - t0, t2 - t1))

    def batch_binder(self):
        """The profile's Bind plugin where the binding cycle of many pods
        can be ONE call of it: it is the only one, and it binds a list
        (``bind_many(pods, node_names)`` -> one Status a pod).  None
        otherwise: a chain of binders decides pod by pod who binds."""
        if len(self.bind_plugins) != 1:
            return None
        p = self.bind_plugins[0]
        return p if hasattr(p, "bind_many") else None

    def binds_bare(self, pods: List[api.Pod]
                   ) -> Tuple[List[bool], Tuple[float, float]]:
        """Per pod: would PreBind, WaitOnPermit and PostBind do nothing
        for it?  (No PreBind or PostBind plugin is ``relevant`` to it
        and it waits on no Permit plugin.)  Such a pod's binding cycle
        is its Bind alone, which ``run_bind_batch`` runs for many.
        Beside the flags, the seconds a pod the walk of PreBind's and of
        PostBind's plugins took: all those points do for such a pod, so
        what ``run_bind_batch`` observes for them."""
        waiting = self.waiting_pods.uids()
        n = max(len(pods), 1)
        t0 = time.time()
        pre = self._any_relevant(self.pre_bind_plugins, pods)
        if waiting:
            pre = [a or pod.uid in waiting for a, pod in zip(pre, pods)]
        t1 = time.time()
        post = self._any_relevant(self.post_bind_plugins, pods)
        t2 = time.time()
        return ([not (a or b) for a, b in zip(pre, post)],
                ((t1 - t0) / n, (t2 - t1) / n))

    @staticmethod
    def _any_relevant(plugins, pods: List[api.Pod]) -> List[bool]:
        """``_relevant`` of any of ``plugins``, a pod."""
        rels = [getattr(p, "relevant", None) for p in plugins]
        if None in rels:            # one of them runs for every pod
            return [True] * len(pods)
        return [any(r(pod) for r in rels) for pod in pods]

    def run_bind_batch(self, binder, pods: List[api.Pod],
                       node_names: List[str], hooks_s=(0.0, 0.0),
                       sink: Optional[list] = None) -> List[Status]:
        """PreBind, Bind and PostBind for pods that ``binds_bare`` said
        yes to, through ``batch_binder``'s plugin: the three points run
        once over all of them instead of all three once a pod.  One
        Status a pod (its Bind's).  The extension-point histogram gets
        what the per-pod walk gives it: one PreBind and one Bind
        observation a pod and one PostBind a pod that bound, same
        labels; a point's seconds are the batch's, shared out over its
        rows (``hooks_s``: PreBind's and PostBind's, from
        ``binds_bare``).  ``sink`` as in ``_timed_point``."""
        m = self.metrics
        if m is None:
            return binder.bind_many(pods, node_names)
        t0 = time.time()
        sts = binder.bind_many(pods, node_names)
        n = len(pods)
        share = (time.time() - t0) / max(n, 1)
        ok = sum(1 for st in sts if st.is_success())
        rows = [(hooks_s[0], "PreBind", "Success")] * n
        if ok == n:
            rows += [(share, "Bind", "Success")] * n
        else:
            rows.extend((share, "Bind", _status_label(st)) for st in sts)
        rows += [(hooks_s[1], "PostBind", "Success")] * ok
        if sink is None:
            m.framework_extension_point_duration.observe_many(rows)
        else:
            sink.extend(rows)
        return sts

    # -- FrameworkHandle surface (reference: interface.go:493) --------------

    def get_waiting_pod(self, uid: str):
        return self.waiting_pods.get(uid)

    def reject_waiting_pod(self, uid: str) -> None:
        wp = self.waiting_pods.get(uid)
        if wp is not None:
            wp.reject("removed")

    def iterate_over_waiting_pods(self, fn) -> None:
        self.waiting_pods.iterate(fn)
