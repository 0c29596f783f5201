"""A configuration file turned into a cluster: plain records for the
reference, API objects for the program, both from the same template data.

Everything a scheduler_perf row states about its pods and nodes is data
in ``perfbench/configs/<name>.json``:

``templates``: name -> one pod template.  Besides ``cpu_milli``,
``memory_bytes`` and ``priority`` a template is written in ONE of two
ways.

  literal (upstream's pod-*.yaml, spelled out)
    ``labels``            a literal map, the same on every pod
    ``pod_affinity`` /    lists of terms, each ``topology_key``,
    ``pod_anti_affinity`` ``match_labels`` and either ``required: true``
                          or a ``weight``
    ``topology_spread``   list of ``max_skew``, ``topology_key``,
                          ``when_unsatisfiable``, ``match_labels``
    ``node_affinity_in``  one required ``In`` term: ``key``, ``values``

  shorthand (the repo's own mirror, ``kubetpu/harness/perf.py:_make_pod``)
    ``group_labels`` and ``features``, a list of ``FEATURES``.  Pod ``i``
    of a role ("init", "measured", "resident", "sample") gets the labels
    ``app=app-<i % group_labels>`` and ``group=<role>``, and each feature
    expands to the term ``_make_pod`` builds for it (``_expand``).

  A template with neither ``features`` nor ``group_labels`` is literal
  and carries exactly the labels it states (none, if it states none); the
  two ways do not mix.  A key outside these raises ``ValueError``.

``init_pods``: ``{template, count}`` or a list of them, built in the
list's order (all of the first template, then the second: upstream's
order), named ``init-<j>`` over the running index and placed by one
seeded round-robin.

``cluster.node_labels``: ``{"<key>": ["v1", "v2", ...]}`` labels node
``i`` with ``values[i % len]`` (upstream's ``labelNodePrepareStrategy``);
``cluster.zones: n`` is the shorthand for ``zone-<i % n>`` under the zone
key and ``region-0`` under the region key.

Not modelled: secrets, persistent volumes and CSI volumes (upstream's PV
rows come after the rows these keys open; ROADMAP B1).

A ``PodRec`` holds all of it as plain tuples and imports nothing of the
program, so a reference can model it; ``api_pod`` builds the API object
from the record alone.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
REGION = "topology.kubernetes.io/region"
# feature name -> what it adds (the selector is the pod's own app group
# for anti/panti/paff, its role group for aff/spread, as in _make_pod)
FEATURES = ("anti", "aff", "panti", "paff", "spread")

Selector = Tuple[Tuple[str, str], ...]        # ((label, value), ...)

_RESOURCE_KEYS = {"cpu_milli", "memory_bytes", "priority"}
_SHORTHAND_KEYS = {"features", "group_labels"}
_LITERAL_KEYS = {"labels", "pod_affinity", "pod_anti_affinity",
                 "topology_spread", "node_affinity_in"}
_TERM_KEYS = {"topology_key", "match_labels", "required", "weight"}
_SPREAD_KEYS = {"max_skew", "topology_key", "when_unsatisfiable",
                "match_labels"}
_WHEN_UNSATISFIABLE = ("DoNotSchedule", "ScheduleAnyway")


@dataclass(frozen=True)
class NodeRec:
    name: str
    cpu_milli: int
    mem_bytes: int
    pods: int
    labels: Dict[str, str]


@dataclass(frozen=True)
class PodRec:
    name: str
    cpu_milli: int
    mem_bytes: int
    priority: int
    labels: Dict[str, str]
    features: Tuple[str, ...] = ()
    # required terms as (topology key, ((label, value),))
    anti_required: Tuple[Tuple[str, Selector], ...] = ()
    aff_required: Tuple[Tuple[str, Selector], ...] = ()
    # preferred terms as (weight, topology key, ((label, value),))
    anti_preferred: Tuple[Tuple[int, str, Selector], ...] = ()
    aff_preferred: Tuple[Tuple[int, str, Selector], ...] = ()
    # (max skew, topology key, when unsatisfiable, ((label, value),))
    spread: Tuple[Tuple[int, str, str, Selector], ...] = ()
    # the one required In term as ((key, (value, ...)),), or ()
    node_affinity_in: Tuple[Tuple[str, Tuple[str, ...]], ...] = ()


# ------------------------------------------------------------------ templates


def _where(config: Dict[str, Any], template: str) -> str:
    return f"config {config.get('name', '?')}: template {template}"


def _selector(where: str, entry: Dict[str, Any]) -> Selector:
    sel = entry.get("match_labels")
    if not isinstance(sel, dict) or not sel:
        raise ValueError(f"{where}: match_labels must be a non-empty map, "
                         f"got {sel!r}")
    return tuple((str(k), str(v)) for k, v in sel.items())


def _unknown(where: str, entry: Dict[str, Any], known) -> None:
    extra = sorted(set(entry) - set(known))
    if extra:
        raise ValueError(f"{where}: unknown key {extra[0]!r}; known: "
                         f"{sorted(known)}")


def _pod_terms(where: str, entries) -> Tuple[tuple, tuple]:
    """(required, preferred) of one ``pod_affinity`` / ``pod_anti_affinity``
    list, each in the list's order."""
    required, preferred = [], []
    for n, entry in enumerate(entries):
        at = f"{where}[{n}]"
        _unknown(at, entry, _TERM_KEYS)
        if "topology_key" not in entry:
            raise ValueError(f"{at}: no topology_key")
        topo, sel = str(entry["topology_key"]), _selector(at, entry)
        if entry.get("required") is True and "weight" not in entry:
            required.append((topo, sel))
        elif "weight" in entry and not entry.get("required"):
            preferred.append((int(entry["weight"]), topo, sel))
        else:
            raise ValueError(f"{at}: a term states either required: true "
                             f"or a weight, got {entry!r}")
    return tuple(required), tuple(preferred)


def _spread(where: str, entries) -> tuple:
    out = []
    for n, entry in enumerate(entries):
        at = f"{where}[{n}]"
        _unknown(at, entry, _SPREAD_KEYS)
        for key in ("max_skew", "topology_key", "when_unsatisfiable"):
            if key not in entry:
                raise ValueError(f"{at}: no {key}")
        if entry["when_unsatisfiable"] not in _WHEN_UNSATISFIABLE:
            raise ValueError(f"{at}: when_unsatisfiable "
                             f"{entry['when_unsatisfiable']!r} is not one "
                             f"of {_WHEN_UNSATISFIABLE}")
        out.append((int(entry["max_skew"]), str(entry["topology_key"]),
                    entry["when_unsatisfiable"], _selector(at, entry)))
    return tuple(out)


def _node_affinity_in(where: str, entry) -> tuple:
    if entry is None:
        return ()
    _unknown(where, entry, {"key", "values"})
    if not entry.get("key") or not entry.get("values"):
        raise ValueError(f"{where}: one In term needs a key and values, "
                         f"got {entry!r}")
    return ((str(entry["key"]), tuple(str(v) for v in entry["values"])),)


def _expand(features: Tuple[str, ...], labels: Dict[str, str]
            ) -> Dict[str, tuple]:
    """The terms ``_make_pod`` builds for each feature name."""
    app = (("app", labels["app"]),)
    grp = (("group", labels["group"]),)
    built = {"anti": ("anti_required", (HOSTNAME, app)),
             "aff": ("aff_required", (ZONE, grp)),
             "panti": ("anti_preferred", (10, ZONE, app)),
             "paff": ("aff_preferred", (10, ZONE, app)),
             "spread": ("spread", (2, ZONE, "DoNotSchedule", grp))}
    return {field: (term,) for field, term in
            (built[f] for f in dict.fromkeys(features))}


class _Template:
    """One ``templates`` entry, checked and parsed once."""

    def __init__(self, config: Dict[str, Any], name: str):
        where = _where(config, name)
        templates = config.get("templates", {})
        if name not in templates:
            raise ValueError(f"{where}: no such template; the file has "
                             f"{sorted(templates)}")
        t = templates[name]
        _unknown(where, t, _RESOURCE_KEYS | _SHORTHAND_KEYS | _LITERAL_KEYS)
        for key in ("cpu_milli", "memory_bytes"):
            if key not in t:
                raise ValueError(f"{where}: no {key}")
        self.cpu_milli = int(t["cpu_milli"])
        self.mem_bytes = int(t["memory_bytes"])
        self.priority = int(t.get("priority", 0))
        self.shorthand = bool(_SHORTHAND_KEYS & set(t))
        self.labels: Dict[str, str] = {}
        self.terms: Dict[str, tuple] = {}
        if self.shorthand:
            mixed = sorted(_LITERAL_KEYS & set(t))
            if mixed:
                raise ValueError(
                    f"{where}: {mixed[0]!r} beside features / group_labels: "
                    "a template is written literally or in the shorthand, "
                    "not both")
            self.groups = int(t.get("group_labels", 10))
            self.features = tuple(t.get("features", ()))
            unknown = [f for f in self.features if f not in FEATURES]
            if unknown:
                raise ValueError(f"{where}: unknown features {unknown}; "
                                 f"known: {FEATURES}")
            # (role, app group) -> (labels, terms), filled as met
            self._by_group: Dict[Tuple[str, int], tuple] = {}
            return
        self.features = ()
        labels = t.get("labels", {})
        if not isinstance(labels, dict):
            raise ValueError(f"{where}: labels must be a map, got {labels!r}")
        self.labels = {str(k): str(v) for k, v in labels.items()}
        aff_req, aff_pref = _pod_terms(f"{where}: pod_affinity",
                                       t.get("pod_affinity", ()))
        anti_req, anti_pref = _pod_terms(f"{where}: pod_anti_affinity",
                                         t.get("pod_anti_affinity", ()))
        self.terms = {
            "anti_required": anti_req, "aff_required": aff_req,
            "anti_preferred": anti_pref, "aff_preferred": aff_pref,
            "spread": _spread(f"{where}: topology_spread",
                              t.get("topology_spread", ())),
            "node_affinity_in": _node_affinity_in(
                f"{where}: node_affinity_in", t.get("node_affinity_in")),
        }

    def record(self, role: str, i: int) -> PodRec:
        labels, terms = self.labels, self.terms
        if self.shorthand:
            key = (role, i % self.groups)
            if key not in self._by_group:
                labels = {"app": f"app-{key[1]}", "group": role}
                self._by_group[key] = (labels,
                                       _expand(self.features, labels))
            labels, terms = self._by_group[key]
        return PodRec(f"{role}-{i}", self.cpu_milli, self.mem_bytes,
                      self.priority, dict(labels), self.features, **terms)


# A set-up builds some hundred thousand records from a handful of
# templates, and drive.py asks for each by (config, name): a config
# dict's templates are parsed once and found again under the dict's
# identity (the entry holds the dict, so the id stays its own).
# ``validate`` parses afresh; a caller that changes a config it has built
# records from makes a new dict, or validates it again.
_parsed: Dict[int, Tuple[Dict[str, Any], Dict[str, _Template]]] = {}


def _template(config: Dict[str, Any], name: str) -> _Template:
    hit = _parsed.get(id(config))
    if hit is None or hit[0] is not config:
        hit = _parsed[id(config)] = (config, {})
    if name not in hit[1]:
        hit[1][name] = _Template(config, name)
    return hit[1][name]


def init_groups(config: Dict[str, Any]) -> List[Tuple[str, int]]:
    """``init_pods`` as [(template, count)...] in the file's order."""
    spec = config.get("init_pods") or []
    entries = [spec] if isinstance(spec, dict) else list(spec)
    out = []
    for n, entry in enumerate(entries):
        at = f"config {config.get('name', '?')}: init_pods[{n}]"
        _unknown(at, entry, {"template", "count"})
        if "template" not in entry or "count" not in entry:
            raise ValueError(f"{at}: needs a template and a count, got "
                             f"{entry!r}")
        if entry["template"] not in config.get("templates", {}):
            raise ValueError(
                f"{at}: template {entry['template']!r} is not in the "
                f"file's templates {sorted(config.get('templates', {}))}")
        out.append((entry["template"], int(entry["count"])))
    return out


def validate(config: Dict[str, Any]) -> None:
    """Everything ``ValueError`` can say about a configuration's cluster,
    templates and pod lists, said before anything is built."""
    node_label_values(config)
    _parsed.pop(id(config), None)
    for name in config.get("templates", {}):
        _template(config, name)
    init_groups(config)
    measured = config.get("measured_pods", {}).get("template")
    if measured not in config.get("templates", {}):
        raise ValueError(
            f"config {config.get('name', '?')}: measured_pods: template "
            f"{measured!r} is not in the file's templates "
            f"{sorted(config.get('templates', {}))}")


# -------------------------------------------------------------------- records


def node_label_values(config: Dict[str, Any]) -> Dict[str, List[str]]:
    """Label key -> the values the nodes take in turn (node ``i`` gets
    ``values[i % len]``), besides the hostname: ``cluster.zones`` spelled
    out, then ``cluster.node_labels``."""
    c = config["cluster"]
    zones = int(c.get("zones", 0))
    table: Dict[str, List[str]] = {}
    if zones:
        table[ZONE] = [f"zone-{z}" for z in range(zones)]
        table[REGION] = ["region-0"]
    for key, values in c.get("node_labels", {}).items():
        at = f"config {config.get('name', '?')}: cluster.node_labels[{key!r}]"
        if not isinstance(values, list) or not values:
            raise ValueError(f"{at}: a non-empty list of values, got "
                             f"{values!r}")
        if key in table or key == HOSTNAME:
            setter = "the node name" if key == HOSTNAME else "cluster.zones"
            raise ValueError(f"{at}: the key is already set by {setter}")
        table[key] = [str(v) for v in values]
    return table


def node_records(config: Dict[str, Any]) -> List[NodeRec]:
    c = config["cluster"]
    shape = c["node"]
    table = node_label_values(config)
    out = []
    for i in range(int(c["nodes"])):
        name = f"node-{i}"
        labels = {HOSTNAME: name}
        for key, values in table.items():
            labels[key] = values[i % len(values)]
        out.append(NodeRec(name, int(shape["cpu_milli"]),
                           int(shape["memory_bytes"]), int(shape["pods"]),
                           labels))
    return out


def pod_record(config: Dict[str, Any], template: str, role: str,
               i: int) -> PodRec:
    return _template(config, template).record(role, i)


def init_placement(config: Dict[str, Any], seed: int) -> List[str]:
    """Node name per init pod: round-robin over a seeded order of the
    nodes, which is what scheduling them with LeastAllocated ends in."""
    n = int(config["cluster"]["nodes"])
    count = sum(c for _, c in init_groups(config))
    order = np.random.default_rng([int(seed), 0x1217]).permutation(n)
    return [f"node-{int(order[j % n])}" for j in range(count)]


def init_records(config: Dict[str, Any], seed: int
                 ) -> List[Tuple[PodRec, str]]:
    templates = [t for t, count in init_groups(config) for _ in range(count)]
    return [(pod_record(config, tmpl, "init", j), node)
            for j, (tmpl, node) in enumerate(
                zip(templates, init_placement(config, seed)))]


def measured_record(config: Dict[str, Any], role: str, i: int) -> PodRec:
    return pod_record(config, config["measured_pods"]["template"], role, i)


# ---------------------------------------------------------------- API objects


def api_node(rec: NodeRec):
    from kubetpu.api import types as api
    alloc = {"cpu": f"{rec.cpu_milli}m", "memory": str(rec.mem_bytes),
             "pods": str(rec.pods)}
    return api.Node(
        metadata=api.ObjectMeta(name=rec.name, labels=dict(rec.labels)),
        status=api.NodeStatus(allocatable=dict(alloc), capacity=dict(alloc)))


def _term(api, topo: str, sel: tuple):
    return api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(sel)),
        topology_key=topo)


def _pod_affinity_side(api, cls, required, preferred):
    """``PodAffinity`` / ``PodAntiAffinity`` of the record's terms, or None
    where it has none on that side."""
    if not required and not preferred:
        return None
    side = cls()
    side.required_during_scheduling_ignored_during_execution.extend(
        _term(api, topo, sel) for topo, sel in required)
    side.preferred_during_scheduling_ignored_during_execution.extend(
        api.WeightedPodAffinityTerm(
            weight=weight, pod_affinity_term=_term(api, topo, sel))
        for weight, topo, sel in preferred)
    return side


def api_pod(rec: PodRec, node: str = ""):
    from kubetpu.api import types as api
    req = {"cpu": f"{rec.cpu_milli}m", "memory": str(rec.mem_bytes)}
    pod = api.Pod(
        metadata=api.ObjectMeta(name=rec.name, labels=dict(rec.labels)),
        spec=api.PodSpec(
            priority=rec.priority,
            containers=[api.Container(
                name="c", image="k8s.gcr.io/pause:3.2",
                resources=api.ResourceRequirements(requests=req))]))
    aff = api.Affinity(
        pod_affinity=_pod_affinity_side(
            api, api.PodAffinity, rec.aff_required, rec.aff_preferred),
        pod_anti_affinity=_pod_affinity_side(
            api, api.PodAntiAffinity, rec.anti_required, rec.anti_preferred))
    for key, values in rec.node_affinity_in:
        aff.node_affinity = api.NodeAffinity(
            required_during_scheduling_ignored_during_execution=(
                api.NodeSelector(node_selector_terms=[api.NodeSelectorTerm(
                    match_expressions=[api.NodeSelectorRequirement(
                        key=key, operator="In", values=list(values))])])))
    if aff.pod_affinity or aff.pod_anti_affinity or aff.node_affinity:
        pod.spec.affinity = aff
    for max_skew, topo, when, sel in rec.spread:
        pod.spec.topology_spread_constraints.append(
            api.TopologySpreadConstraint(
                max_skew=max_skew, topology_key=topo,
                when_unsatisfiable=when,
                label_selector=api.LabelSelector(match_labels=dict(sel))))
    if node:
        pod.spec.node_name = node
    return pod


def build_store(nodes: List[NodeRec], bound: List[Tuple[PodRec, str]]):
    """A ClusterStore holding the nodes and the already-bound pods."""
    from kubetpu.client.store import ClusterStore
    store = ClusterStore()
    for n in nodes:
        store.add(api_node(n))
    for rec, node in bound:
        store.add(api_pod(rec, node))
    return store


def scheduler_config(section: Dict[str, Any], mesh_shape=None):
    """``scheduler`` (or ``check.scheduler``) of a configuration file as
    the program's component config: every key is a field of
    KubeSchedulerConfiguration, nothing else is set."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    kw = dict(section)
    if mesh_shape:
        kw["mesh_shape"] = tuple(mesh_shape)
    return KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                      **kw)
