"""Cluster snapshot tensorization: NodeInfos -> dense device arrays.

This is the TPU-native analog of the reference's scheduler cache snapshot
(reference: pkg/scheduler/internal/cache/snapshot.go:29 Snapshot,
cache.go:202 UpdateSnapshot): instead of a list of NodeInfo pointers handed
to 16 goroutines, the cluster becomes a struct-of-arrays over the node axis
(plus an existing-pods axis for affinity/spread) that one jitted program
consumes.  All strings are interned (kubetpu/utils/intern.py); all set
membership is multi-hot.

Unit conventions (chosen so every value the scheduler compares is exact in
f32 — see kubetpu/api/resource.py):
  channel 0: CPU millicores          (raw int value)
  channel 1: memory MiB              (bytes / 2^20; exact for Mi-granular values)
  channel 2: ephemeral-storage MiB
  channel 3: pod count / max pods
  channel 4+: scalar (extended) resources, raw integer value, one channel
              per interned resource name.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..api import types as api
from ..api.resource import Resource
from ..framework.types import NodeInfo, PodInfo
from ..ops.selectors import (FIELD_PREFIX, SelectorCompiler, SelectorSet,
                             selector_key)
from ..utils.intern import InternTable, pow2_bucket

MIB = float(2 ** 20)

# fixed channels
CH_CPU, CH_MEM, CH_EPH, CH_PODS = 0, 1, 2, 3
N_FIXED_CHANNELS = 4

# taint effect codes
EFFECT_CODES = {api.TAINT_EFFECT_NO_SCHEDULE: 0,
                api.TAINT_EFFECT_PREFER_NO_SCHEDULE: 1,
                api.TAINT_EFFECT_NO_EXECUTE: 2}


def resource_to_channels(r: Resource, table: InternTable, R: int,
                         intern_new: bool = True) -> np.ndarray:
    out = np.zeros((R,), np.float32)
    out[CH_CPU] = r.milli_cpu
    out[CH_MEM] = r.memory / MIB
    out[CH_EPH] = r.ephemeral_storage / MIB
    out[CH_PODS] = r.allowed_pod_number
    for name, v in r.scalar_resources.items():
        i = table.rname.intern(name) if intern_new else table.rname.get(name)
        ch = N_FIXED_CHANNELS + i
        if 0 <= i and ch < R:
            out[ch] = v
    return out


class ExistingTerms(NamedTuple):
    """Flattened (anti-)affinity terms owned by *existing* pods, matched
    against incoming pods.  Two instances live in ClusterTensors: one for
    filtering (required anti-affinity of existing pods, reference:
    interpodaffinity/filtering.go:166 getExistingAntiAffinityCounts) and one
    for scoring (preferred +w / -w and required-affinity x hardWeight,
    reference: interpodaffinity/scoring.go:128 processExistingPod)."""
    sel: SelectorSet           # [Et] selectors over incoming-pod labels
    ns_hot: jnp.ndarray        # [Et, NS] f32 — namespaces the term applies to
    topo_key: jnp.ndarray      # [Et] i32 index into topokey axis
    pod_idx: jnp.ndarray       # [Et] i32 owning existing-pod row
    weight: jnp.ndarray        # [Et] f32 (signed; 1.0 for filter terms)
    valid: jnp.ndarray         # [Et] bool


# which of an owner's term lists each of the two tables takes
TERM_KINDS = {"filter_terms": "filter", "score_terms": "score"}
# the leaves with one entry a ROW of a term table, in TermsDelta's order
# (``sel.index`` first); the rest of ``sel`` has one entry a UNIQUE selector
TERM_SLOTS = ("ns_hot", "topo_key", "pod_idx", "weight", "valid")


class ClusterTensors(NamedTuple):
    """One immutable device-side cluster snapshot (a JAX pytree)."""
    # node axis ------------------------------------------------------------
    allocatable: jnp.ndarray        # [N, R] f32
    requested: jnp.ndarray          # [N, R] f32
    nonzero_requested: jnp.ndarray  # [N, 2] f32 (cpu milli, mem MiB)
    node_valid: jnp.ndarray         # [N] bool
    unschedulable: jnp.ndarray      # [N] bool (.spec.unschedulable)
    kv: jnp.ndarray                 # [N, L] bool — node has label (k,v)
    keymask: jnp.ndarray            # [N, K] bool — node has label key
    num: jnp.ndarray                # [N, K] f32 — numeric label value (+inf
                                    # when absent/non-numeric: keeps cluster
                                    # tensors NaN-free so the sanitizer's
                                    # jax_debug_nans pass stays meaningful;
                                    # selectors guard with isfinite)
    topo_pair: jnp.ndarray          # [N, TK] i32 — kv id of (topokey, value), -1 absent
    taints: jnp.ndarray             # [N, T] bool
    ports: jnp.ndarray              # [N, P] bool
    images: jnp.ndarray             # [N, I] bool
    avoid_hot: jnp.ndarray          # [N, AV] bool — node's preferAvoidPods entries
                                    #   over the (controller kind, uid) vocab
    zone_hot: jnp.ndarray           # [N, Z] f32 one-hot over the ZONE vocab
                                    #   (Z = pow2 zone-count bucket, NOT N:
                                    #   zone aggregation must stay a tiny
                                    #   [., Z] matmul — an [N, N] one-hot
                                    #   made DefaultPodTopologySpread's
                                    #   normalize the single most expensive
                                    #   op at 8k nodes)
    # vocab-side metadata ---------------------------------------------------
    taint_is_hard: jnp.ndarray      # [T] bool (NoSchedule | NoExecute)
    taint_is_prefer: jnp.ndarray    # [T] bool (PreferNoSchedule)
    image_size: jnp.ndarray         # [I] f32 bytes
    image_spread: jnp.ndarray       # [I] f32 fraction of nodes having the image
    # existing pods axis ----------------------------------------------------
    pod_kv: jnp.ndarray             # [P, L] bool
    pod_key: jnp.ndarray            # [P, K] bool
    pod_ns_hot: jnp.ndarray         # [P, NS] f32 one-hot
    pod_node: jnp.ndarray           # [P] i32 node row (-1 invalid)
    pod_valid: jnp.ndarray          # [P] bool
    pod_terminating: jnp.ndarray    # [P] bool (deletionTimestamp set)
    # existing pods' terms --------------------------------------------------
    filter_terms: ExistingTerms     # required anti-affinity (filter)
    score_terms: ExistingTerms      # preferred +/-, required x hardWeight (score)

    @property
    def n_nodes_cap(self) -> int:
        return self.allocatable.shape[0]

    @property
    def nbytes(self) -> int:
        """Bytes of every leaf, from shapes and dtypes: no transfer."""
        return sum(int(x.nbytes) for x in jax.tree.leaves(self))


class HostClusterArrays(NamedTuple):
    """Numpy twin of ClusterTensors (what the builder maintains).

    The two label one-hots (kv [N, L], pod_kv [P, L]) are held COMPACT as
    [., ML] i32 id lists and densified on device at to_device time: at 8k
    nodes L is ~16k (hostname values), so the dense bools are ~134 MB each
    while the id lists are ~0.5 MB, so a fresh-world upload moves
    hundreds of times fewer bytes (upload bandwidth on the chip: not
    measured)."""
    arrays: dict

    def to_device(self) -> ClusterTensors:
        a = self.arrays
        L = a["_kv_cap"]
        vals = [None if f in ("kv", "pod_kv") else a[f]
                for f in ClusterTensors._fields]
        # jnp.array, NOT jnp.asarray: asarray zero-copies a 64-byte-
        # aligned numpy buffer on CPU, and the delta scatter DONATES the
        # cluster (programs.apply_cluster_delta) — XLA then reuses the
        # aliased buffer for unrelated outputs and silently corrupts the
        # HOST MIRROR these arrays belong to.  Small mirrors only align
        # by malloc luck (flaky); production-sized ones are page-aligned
        # (always).  Caught by the anti-entropy verifier's false-positive
        # divergences; the copy is paid once per resync.
        dev = jax.tree.map(lambda x: x if x is None else jnp.array(x),
                           ClusterTensors(*vals),
                           is_leaf=lambda x: x is None)
        return dev._replace(kv=_densify_ids(jnp.asarray(a["_kv_ids"]), L),
                            pod_kv=_densify_ids(jnp.asarray(a["_pod_kv_ids"]),
                                                L))


@functools.partial(jax.jit, static_argnames=("L",))
def _densify_ids(ids, L: int):
    """[X, ML] i32 id lists (-1 pad) -> [X, L] bool multi-hot, on device."""
    X = ids.shape[0]
    rows = jnp.arange(X)[:, None]
    return jnp.zeros((X, L), bool).at[
        rows, jnp.clip(ids, 0, L - 1)].max((ids >= 0) & (ids < L))


# Well-known topology keys are always present so zone/hostname spreading
# needs no vocab growth (reference: pkg/apis/core/v1/well_known_labels.go).
SEED_TOPOKEYS = (api.LABEL_HOSTNAME, api.LABEL_ZONE, api.LABEL_REGION,
                 api.LABEL_ZONE_LEGACY, api.LABEL_REGION_LEGACY)


class SnapshotBuilder:
    """Builds HostClusterArrays from a list of NodeInfos.

    Mirrors the roles of snapshot.go:49 (NewSnapshot) — including the
    HavePodsWithAffinityList secondary index, which here becomes the
    flattened ExistingTerms tensors.  DefaultHardPodAffinityWeight = 1
    (reference: apis/config/v1beta1/defaults.go hardPodAffinityWeight).
    """

    def __init__(self, table: Optional[InternTable] = None,
                 hard_pod_affinity_weight: int = 1):
        self.table = table or InternTable()
        for k in SEED_TOPOKEYS:
            self.table.topokey.intern(k)
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self.compiler = SelectorCompiler(self.table)

    # -- interning helpers --------------------------------------------------

    def _intern_node_strings(self, nodes: List[NodeInfo]) -> None:
        """First pass: make sure vocab contains everything in the cluster so
        bucket caps are final before array allocation.  The three parts are
        the three things a mirror row is filled FROM (the fills below take
        the same split), so the delta path interns a part only when it
        fills from it: what it kept has its ids already."""
        for ni in nodes:
            if ni.node is None:
                continue
            self.intern_node(ni)
            self.intern_node_usage(ni)
            for pi in ni.pods:
                self.intern_pod(pi)

    def intern_node(self, ni: NodeInfo) -> None:
        """The strings ``fill_node_static`` reads: the Node object's, and
        what ``NodeInfo.set_node`` derives from it."""
        t = self.table
        node = ni.node
        for k, v in node.metadata.labels.items():
            t.kv.intern((k, v)); t.key.intern(k)
        t.kv.intern((FIELD_PREFIX + "metadata.name", node.name))
        t.key.intern(FIELD_PREFIX + "metadata.name")
        for taint in node.spec.taints:
            t.taint.intern((taint.key, taint.value, taint.effect))
        for name in ni.image_states:
            t.image.intern(_norm_image(name))
        for r in ni.allocatable.scalar_resources:
            t.rname.intern(r)
        zk = zone_key(node)
        if zk:
            t.zone.intern(zk)
        for kind, uid in _avoid_entries(node):
            t.avoid.intern((kind, uid))

    def intern_node_usage(self, ni: NodeInfo) -> None:
        """The strings ``fill_node_usage`` reads: the host ports in use."""
        t = self.table
        for triple in ni.used_ports:
            for pid in _port_ids_node(triple):
                t.port.intern(pid)

    def intern_pod(self, pi: PodInfo) -> None:
        """The strings ``fill_pod_row`` reads, and those an existing pod's
        terms compile from (``_build_terms``)."""
        t = self.table
        p = pi.pod
        t.ns.intern(p.namespace)
        for k, v in p.metadata.labels.items():
            t.kv.intern((k, v)); t.key.intern(k)
        for term in (pi.required_anti_affinity_terms
                     + [w.term for w in pi.preferred_affinity_terms]
                     + [w.term for w in pi.preferred_anti_affinity_terms]
                     + pi.required_affinity_terms):
            t.topokey.intern(term.topology_key)
            for ns in term.namespaces:
                t.ns.intern(ns)

    def intern_pending(self, pods: List[PodInfo]) -> None:
        """Pre-intern the strings of *pending* pods so vocab capacities are
        final before snapshot arrays are sized.  Without this, two batch pods
        sharing a label or hostPort that exists nowhere else in the cluster
        could not see each other in the intra-batch (scan) interactions."""
        t = self.table
        for pi in pods:
            p = pi.pod
            t.ns.intern(p.namespace)
            for k, v in p.metadata.labels.items():
                t.kv.intern((k, v)); t.key.intern(k)
            for c in p.spec.containers:
                for port in c.ports:
                    if port.host_port <= 0:
                        continue
                    triple = (port.protocol or "TCP", port.host_ip or "0.0.0.0",
                              port.host_port)
                    for pid in _port_ids_node(triple) + port_ids_pod(triple):
                        t.port.intern(pid)
            for term in (pi.required_affinity_terms + pi.required_anti_affinity_terms
                         + [w.term for w in pi.preferred_affinity_terms]
                         + [w.term for w in pi.preferred_anti_affinity_terms]):
                t.topokey.intern(term.topology_key)
                for ns in term.namespaces:
                    t.ns.intern(ns)
            for c in p.spec.topology_spread_constraints:
                t.topokey.intern(c.topology_key)

    # -- build --------------------------------------------------------------

    def build(self, nodes: List[NodeInfo]) -> HostClusterArrays:
        self._intern_node_strings(nodes)
        t = self.table
        N = pow2_bucket(len(nodes), 8)
        R = N_FIXED_CHANNELS + t.rname.cap
        L, K, TK = t.kv.cap, t.key.cap, t.topokey.cap
        T, P, I, NS = t.taint.cap, t.port.cap, t.image.cap, t.ns.cap
        AV = t.avoid.cap
        n_pods = sum(len(ni.pods) for ni in nodes)
        PP = pow2_bucket(n_pods, 8)
        # compact label-id forms of kv/pod_kv (densified on device)
        MLn = pow2_bucket(max((len(ni.node.metadata.labels) + 1
                               for ni in nodes if ni.node is not None),
                              default=1), 4)
        MLp = pow2_bucket(max((len(pi.pod.metadata.labels)
                               for ni in nodes for pi in ni.pods),
                              default=1), 4)

        d: dict = {
            "allocatable": np.zeros((N, R), np.float32),
            "requested": np.zeros((N, R), np.float32),
            "nonzero_requested": np.zeros((N, 2), np.float32),
            "node_valid": np.zeros((N,), bool),
            "unschedulable": np.zeros((N,), bool),
            "_kv_ids": np.full((N, MLn), -1, np.int32),
            "_pod_kv_ids": np.full((PP, MLp), -1, np.int32),
            "_kv_cap": L,
            "keymask": np.zeros((N, K), bool),
            "num": np.full((N, K), np.inf, np.float32),
            "topo_pair": np.full((N, TK), -1, np.int32),
            "taints": np.zeros((N, T), bool),
            "ports": np.zeros((N, P), bool),
            "images": np.zeros((N, I), bool),
            "avoid_hot": np.zeros((N, AV), bool),
            "zone_hot": np.zeros((N, t.zone.cap), np.float32),
            "taint_is_hard": np.zeros((T,), bool),
            "taint_is_prefer": np.zeros((T,), bool),
            "image_size": np.zeros((I,), np.float32),
            "image_spread": np.zeros((I,), np.float32),
            "pod_key": np.zeros((PP, K), bool),
            "pod_ns_hot": np.zeros((PP, NS), np.float32),
            "pod_node": np.full((PP,), -1, np.int32),
            "pod_valid": np.zeros((PP,), bool),
            "pod_terminating": np.zeros((PP,), bool),
        }

        # vocab metadata
        for i in range(len(t.taint)):
            _, _, effect = t.taint.key(i)
            d["taint_is_hard"][i] = effect in (api.TAINT_EFFECT_NO_SCHEDULE,
                                               api.TAINT_EFFECT_NO_EXECUTE)
            d["taint_is_prefer"][i] = effect == api.TAINT_EFFECT_PREFER_NO_SCHEDULE

        image_nodes = np.zeros((I,), np.float32)
        pod_row = 0
        pod_rows: Dict[str, int] = {}  # pod uid -> row
        filter_owners: List[Tuple[PodInfo, int]] = []
        score_owners: List[Tuple[PodInfo, int]] = []

        for n_idx, ni in enumerate(nodes):
            node = ni.node
            if node is None:
                continue
            fill_node_static(d, n_idx, ni, t)
            fill_node_usage(d, n_idx, ni, t)
            for ii in np.nonzero(d["images"][n_idx])[0]:
                image_nodes[ii] += 1

            for pi in ni.pods:
                fill_pod_row(d, pod_row, pi, n_idx, t)
                pod_rows[pi.pod.uid] = pod_row
                if pi.required_anti_affinity_terms:
                    filter_owners.append((pi, pod_row))
                if (pi.preferred_affinity_terms or pi.preferred_anti_affinity_terms
                        or pi.required_affinity_terms):
                    score_owners.append((pi, pod_row))
                pod_row += 1

        n_valid = max(float(len(nodes)), 1.0)
        d["image_spread"] = image_nodes / n_valid

        # delta-maintenance metadata (state/delta.py DeltaTensorizer):
        # stable row assignments + per-image node counts, so incremental
        # updates can start exactly where this build left off; for each
        # term table the rows of every owner and the unique row of every
        # selector key (TermTable)
        d["_term_rows"] = {}
        for field, owners in (("filter_terms", filter_owners),
                              ("score_terms", score_owners)):
            d[field], d["_term_rows"][field] = self._build_terms(
                owners, kind=TERM_KINDS[field])
        d["_pod_rows"] = pod_rows
        d["_image_nodes"] = image_nodes
        return HostClusterArrays(arrays=d)

    def _build_terms(self, owners: List[Tuple[PodInfo, int]], kind: str
                     ) -> Tuple[ExistingTerms, dict]:
        """The owners' terms packed in the order given, and what a
        TermTable keeps such a table by: ``owner_rows`` (uid -> its rows)
        and ``sel_keys`` (selector key -> unique row)."""
        t = self.table
        NS = t.ns.cap
        sels, nss, topos, pods, weights = [], [], [], [], []
        owner_rows: Dict[str, Tuple[int, ...]] = {}
        for pi, row in owners:
            at = len(sels)
            for term, weight in owner_terms(pi, kind,
                                            self.hard_pod_affinity_weight):
                sels.append(term.selector)
                nss.append(term.namespaces)
                topos.append(t.topokey.get(term.topology_key))
                pods.append(row)
                weights.append(weight)
            if len(sels) > at:
                owner_rows[pi.pod.uid] = tuple(range(at, len(sels)))

        Et = pow2_bucket(len(sels), 1)
        sel_keys: dict = {}
        sel_set = self.compiler.compile(sels + [None] * (Et - len(sels)),
                                        pad_s=Et, keys_out=sel_keys)
        ns_hot = np.zeros((Et, NS), np.float32)
        topo_key = np.zeros((Et,), np.int32)
        pod_idx = np.zeros((Et,), np.int32)
        weight = np.zeros((Et,), np.float32)
        valid = np.zeros((Et,), bool)
        for i in range(len(sels)):
            for ns in nss[i]:
                j = t.ns.get(ns)
                if j >= 0:
                    ns_hot[i, j] = 1.0
            topo_key[i] = max(topos[i], 0)
            pod_idx[i] = pods[i]
            weight[i] = weights[i]
            valid[i] = True
        return (ExistingTerms(sel=sel_set, ns_hot=ns_hot, topo_key=topo_key,
                              pod_idx=pod_idx, weight=weight, valid=valid),
                {"owner_rows": owner_rows, "sel_keys": sel_keys})


def owner_terms(owner, kind: str, hard_pod_affinity_weight: int) -> list:
    """(term, weight) of the rows an existing pod gives one table, in row
    order: ``filter`` its required anti-affinity terms (reference:
    filtering.go:166), ``score`` its preferred terms at their signed
    weights and its required affinity terms at the hard weight, none at
    weight 0 (scoring.go:128).  ``owner``: a PodInfo or a TermOwner."""
    if kind == "filter":
        return [(term, 1.0) for term in owner.required_anti_affinity_terms]
    out = [(w.term, float(w.weight)) for w in owner.preferred_affinity_terms]
    out += [(w.term, -float(w.weight))
            for w in owner.preferred_anti_affinity_terms]
    if hard_pod_affinity_weight:
        out += [(term, float(hard_pod_affinity_weight))
                for term in owner.required_affinity_terms]
    return out


def term_slots(terms: ExistingTerms) -> tuple:
    """The per-row leaves of a term table, ``sel.index`` first: what a
    TermsDelta scatters into."""
    return (terms.sel.index,) + tuple(getattr(terms, f) for f in TERM_SLOTS)


def with_term_slots(terms: ExistingTerms, slots) -> ExistingTerms:
    """``terms`` with its per-row leaves replaced (term_slots' order)."""
    return terms._replace(sel=terms.sel._replace(index=slots[0]),
                          **dict(zip(TERM_SLOTS, slots[1:])))


class TermsDelta(NamedTuple):
    """The rows of ONE term table that a cycle wrote (appended or
    tombstoned), gathered from the mirror after the writes: applied on
    device by models/programs.py apply_terms_delta with
    ``x.at[rows].set(..., mode="drop")``.  ``rows`` is padded to its
    bucket with the table's row count, one past capacity, which "drop"
    discards (ClusterDelta says why not -1)."""
    rows: np.ndarray               # [Dt] i32 (pad = Et: dropped)
    sel_index: np.ndarray          # [Dt] i32
    ns_hot: np.ndarray             # [Dt, NS] f32
    topo_key: np.ndarray           # [Dt] i32
    pod_idx: np.ndarray            # [Dt] i32
    weight: np.ndarray             # [Dt] f32
    valid: np.ndarray              # [Dt] bool


def gather_terms_delta(terms: ExistingTerms, rows, floor: int) -> TermsDelta:
    """Slice ``rows`` (no duplicates) of a mirror table into a TermsDelta
    of at least ``floor`` rows, a power of two."""
    rows = np.asarray(rows, np.intp)
    Dt = pow2_bucket(len(rows), floor)
    r = np.full((Dt,), terms.valid.shape[0], np.int32)
    r[:len(rows)] = rows

    def g(arr):
        out = np.zeros((Dt,) + arr.shape[1:], arr.dtype)
        out[:len(rows)] = arr[rows]
        return out
    return TermsDelta(r, *map(g, term_slots(terms)))


class TermTable:
    """ONE existing-term table of the host mirror (``arrays[field]``) kept
    by ROW between builds: an owner that comes compiles ITS terms into
    free rows (lowest first, as pod rows are handed out), one that goes
    tombstones its rows, every leaf back to what a build's padding row
    holds.  So the table is no function of the ORDER owners are walked in:
    it holds the same multiset of valid rows as a fresh ``_build_terms``
    (each row read through its selector's requirement content and its
    owner's uid, not through ``sel.index`` and ``pod_idx``), and every
    other row is a padding row.  Every consumer masks by ``valid``
    (ops/kernels.py existing_terms_match, _owner_pairs) and sums integer
    weights in f32, so row order moves no placement.

    ``Et`` is the pow2 bucket of the high-water row and never shrinks;
    the unique-selector leaves grow a row for a selector key not seen
    since the build, and nothing is ever taken out of them.  A build
    (the DeltaTensorizer's resync) is what packs the table again."""

    def __init__(self, builder: SnapshotBuilder, arrays: dict, field: str):
        meta = arrays["_term_rows"][field]
        self.builder = builder
        self.arrays = arrays
        self.field = field
        self.kind = TERM_KINDS[field]
        self.owner_rows: Dict[str, Tuple[int, ...]] = meta["owner_rows"]
        self.sel_keys: dict = meta["sel_keys"]
        self.live = self.high = sum(map(len, self.owner_rows.values()))
        self.free: List[int] = []            # kept sorted, pop lowest
        self._whole = False                  # update()'s, set as it grows

    @property
    def terms(self) -> ExistingTerms:
        return self.arrays[self.field]

    def update(self, went, came) -> Tuple[Optional[np.ndarray], int]:
        """Tombstone the rows of the owners ``went`` (uids), then compile
        the owners that ``came`` (TermOwners) into free rows, all with one
        fancy-indexed assignment a leaf.  Returns (the rows written, None
        where the table changed SHAPE or gained a unique selector and has
        to cross to the device whole; rows tombstoned + rows appended)."""
        t = self.builder.table
        hw = self.builder.hard_pod_affinity_weight
        self._whole = False
        tomb = [r for uid in went for r in self.owner_rows.pop(uid, ())]
        sel_u, topos, pods, weights, ns_at, ns_id = [], [], [], [], [], []
        counts = []
        for o in came:
            at = len(sel_u)
            for term, weight in owner_terms(o, self.kind, hw):
                key = selector_key(term.selector)
                u = self.sel_keys.get(key, -1)
                if u < 0:
                    u = self._add_unique(key)
                for ns in term.namespaces:
                    j = t.ns.get(ns)
                    if j >= 0:
                        ns_at.append(len(sel_u))
                        ns_id.append(j)
                sel_u.append(u)
                topos.append(max(t.topokey.get(term.topology_key), 0))
                pods.append(o.row)
                weights.append(weight)
            if len(sel_u) > at:
                counts.append((o.uid, len(sel_u) - at))
        if tomb:
            nil = self._nil()
            for leaf in term_slots(self.terms):
                leaf[tomb] = 0
            self.terms.sel.index[tomb] = nil
            self.free.extend(tomb)
            self.free.sort()
        n = len(sel_u)
        rows = self.free[:n]
        del self.free[:n]
        if n > len(rows):
            top = self.high + n - len(rows)
            rows.extend(range(self.high, top))
            self.high = top
            if top > self.terms.valid.shape[0]:
                self._grow_rows(pow2_bucket(top, 1))
        if n:
            terms = self.terms
            at = np.asarray(rows, np.intp)
            terms.sel.index[at] = sel_u
            terms.ns_hot[at[ns_at], ns_id] = 1.0
            terms.topo_key[at] = topos
            terms.pod_idx[at] = pods
            terms.weight[at] = weights
            terms.valid[at] = True
            k = 0
            for uid, c in counts:
                self.owner_rows[uid] = tuple(rows[k:k + c])
                k += c
        self.live += n - len(tomb)
        written = (None if self._whole
                   else np.union1d(tomb, rows).astype(np.intp))
        return written, len(tomb) + n

    def _nil(self) -> int:
        """The unique row of the selector that matches nothing, which
        padding rows name: a build that filled its bucket compiled none."""
        nil = self.sel_keys.get(None, -1)
        return nil if nil >= 0 else self._add_unique(None)

    def _add_unique(self, key: Optional[tuple]) -> int:
        """A unique-selector row for a key the table has not compiled
        since its build: the next row of the unique leaves, which grow to
        the next bucket of U (rows) or Q (requirements) where it does not
        fit.  The device holds no such row yet and the scatter carries
        none: the table crosses whole."""
        self._whole = True
        terms = self.terms
        sel = terms.sel
        u = len(self.sel_keys)
        U, Q = sel.req_valid.shape
        need = (pow2_bucket(u + 1, 1), max(Q, pow2_bucket(len(key or ()), 2)))
        if need != (U, Q):
            def pad(x):
                width = [(0, need[0] - U)] + [(0, 0)] * (x.ndim - 1)
                if x.ndim > 1:
                    width[1] = (0, need[1] - Q)
                return np.pad(x, width)
            sel = SelectorSet(index=sel.index, **{
                f: pad(getattr(sel, f)) for f in SelectorSet._fields
                if f != "index"})
            self.arrays[self.field] = terms._replace(sel=sel)
        self.builder.compiler.fill_unique(sel, u, key)
        self.sel_keys[key] = u
        return u

    def _grow_rows(self, Et: int) -> None:
        """Pad the per-row leaves with padding rows up to ``Et``."""
        self._whole = True
        nil = self._nil()
        terms = self.terms
        n = Et - terms.valid.shape[0]
        slots = [np.concatenate([x, np.zeros((n,) + x.shape[1:], x.dtype)])
                 for x in term_slots(terms)]
        slots[0][-n:] = nil
        self.arrays[self.field] = with_term_slots(terms, slots)


# --------------------------------------------------------------------------
# Per-row fills, shared by SnapshotBuilder.build (the from-scratch walk) and
# state/delta.py DeltaTensorizer (the incremental path).  Bit-exactness
# contract: filling a node or pod row through these helpers produces
# byte-identical arrays to a fresh build of the same NodeInfo against the
# same InternTable, so delta-maintained tensors never drift from a rebuild.
# The two term tables are kept by row (TermTable above) and equal a fresh
# build's as MULTISETS of valid rows, every other row a padding row.


def fill_node_static(d: dict, n_idx: int, ni: NodeInfo,
                     t: InternTable) -> None:
    """The node-axis rows that are a function of the Node object and of
    what ``NodeInfo.set_node`` derives from it (``allocatable``,
    ``image_states``): nothing a pod's coming or going moves, so the
    delta path refills them only for a node whose ``node_generation``
    moved.  ``topo_pair`` also reads the topology-key LIST, which no
    marker covers and none has to: ``vocab_signature`` carries the
    list's length, so a key interned since the row was filled is a
    resync before any row is read.  Clears each row first so refilling a
    previously-populated one leaves no stale label/taint/image bits
    behind."""
    node = ni.node
    R = d["allocatable"].shape[1]
    d["node_valid"][n_idx] = True
    d["unschedulable"][n_idx] = node.spec.unschedulable
    d["_kv_ids"][n_idx] = -1
    d["keymask"][n_idx] = False
    d["num"][n_idx] = np.inf
    d["topo_pair"][n_idx] = -1
    d["taints"][n_idx] = False
    d["images"][n_idx] = False
    d["avoid_hot"][n_idx] = False
    d["zone_hot"][n_idx] = 0.0
    d["allocatable"][n_idx] = resource_to_channels(ni.allocatable, t, R)
    labels = dict(node.metadata.labels)
    labels[FIELD_PREFIX + "metadata.name"] = node.name
    for li, (k, v) in enumerate(labels.items()):
        d["_kv_ids"][n_idx, li] = t.kv.get((k, v))
        ki = t.key.get(k)
        d["keymask"][n_idx, ki] = True
        try:
            d["num"][n_idx, ki] = float(int(v))
        except ValueError:
            pass
    for tk_i in range(len(t.topokey)):
        tk = t.topokey.key(tk_i)
        if tk in labels:
            d["topo_pair"][n_idx, tk_i] = t.kv.get((tk, labels[tk]))
    for taint in node.spec.taints:
        d["taints"][n_idx, t.taint.get((taint.key, taint.value,
                                        taint.effect))] = True
    for name, size in ni.image_states.items():
        ii = t.image.get(_norm_image(name))
        d["images"][n_idx, ii] = True
        d["image_size"][ii] = size
    for kind, uid in _avoid_entries(node):
        d["avoid_hot"][n_idx, t.avoid.get((kind, uid))] = True
    zk = zone_key(node)
    if zk:
        d["zone_hot"][n_idx, t.zone.get(zk)] = 1.0


def fill_node_usage(d: dict, n_idx: int, ni: NodeInfo,
                    t: InternTable) -> None:
    """The node-axis rows a pod's coming or going moves: what the node's
    pods request (with their count) and the host ports they hold."""
    req = resource_to_channels(ni.requested, t, d["requested"].shape[1])
    req[CH_PODS] = len(ni.pods)
    d["requested"][n_idx] = req
    d["nonzero_requested"][n_idx, 0] = ni.non_zero_requested.milli_cpu
    d["nonzero_requested"][n_idx, 1] = ni.non_zero_requested.memory / MIB
    d["ports"][n_idx] = False
    for triple in ni.used_ports:
        for pid in _port_ids_node(triple):
            d["ports"][n_idx, t.port.get(pid)] = True


def fill_pod_row(d: dict, row: int, pi: PodInfo, n_idx: int,
                 t: InternTable) -> None:
    """(Re)fill one existing-pod row.  Clears first (delta row reuse)."""
    clear_pod_row(d, row)
    p = pi.pod
    d["pod_node"][row] = n_idx
    d["pod_valid"][row] = True
    d["pod_terminating"][row] = p.metadata.deletion_timestamp is not None
    d["pod_ns_hot"][row, t.ns.get(p.namespace)] = 1.0
    for li, (k, v) in enumerate(p.metadata.labels.items()):
        d["_pod_kv_ids"][row, li] = t.kv.get((k, v))
        d["pod_key"][row, t.key.get(k)] = True


def clear_pod_row(d: dict, row: int) -> None:
    """Reset a pod row to build-time defaults (an evicted pod's freed row
    must be byte-identical to a fresh build's padding row)."""
    d["pod_node"][row] = -1
    d["pod_valid"][row] = False
    d["pod_terminating"][row] = False
    d["pod_ns_hot"][row] = 0.0
    d["_pod_kv_ids"][row] = -1
    d["pod_key"][row] = False


def vocab_signature(table: InternTable) -> tuple:
    """Every width the cluster tensors are sized with: each vocab's pow2
    cap (zone included) plus the topokey LENGTH — ``topo_pair`` columns
    are filled from the key LIST at build time, so topokey growth inside
    the cap still invalidates built tensors.  The ONE signature both
    resident-state guards compare (the scheduler's gang chain and the
    DeltaTensorizer): a vocab added here invalidates both, never one."""
    caps = tuple((n, getattr(table, n).cap) for n in
                 ("kv", "key", "ns", "topokey", "rname", "port", "taint",
                  "image", "avoid", "zone"))
    return caps + (("topokey_len", len(table.topokey)),)


def pod_has_terms(pi: PodInfo, hard_pod_affinity_weight: int = 1) -> bool:
    """True when this existing pod contributes rows to filter_terms or
    score_terms: a term OWNER, whose coming or going the delta path
    writes into the two tables by row (TermTable)."""
    return bool(pi.required_anti_affinity_terms
                or pi.preferred_affinity_terms
                or pi.preferred_anti_affinity_terms
                or (hard_pod_affinity_weight and pi.required_affinity_terms))


class ClusterDelta(NamedTuple):
    """Compact [D]-indexed update tables for one cycle's dirty rows,
    applied on device by models/programs.py apply_cluster_delta
    (``x.at[rows].set(..., mode="drop")``).  Row vectors are padded to a
    pow2 bucket with ONE-PAST-CAPACITY indices (N for node rows, P for pod
    rows): "drop" mode discards out-of-bounds scatters, while a -1 pad
    would WRAP to the last row and corrupt it.  Label one-hots ride as
    compact id lists ([D, ML] i32) and densify on device, mirroring the
    HostClusterArrays transfer contract.  The two [I] image vectors are
    cluster-global (spread is a fraction of all nodes) and tiny, so every
    delta replaces them wholesale."""
    node_rows: np.ndarray          # [Dn] i32 (pad = N: dropped)
    allocatable: np.ndarray        # [Dn, R] f32
    requested: np.ndarray          # [Dn, R] f32
    nonzero_requested: np.ndarray  # [Dn, 2] f32
    node_valid: np.ndarray         # [Dn] bool
    unschedulable: np.ndarray      # [Dn] bool
    kv_ids: np.ndarray             # [Dn, MLn] i32 (densified on device)
    keymask: np.ndarray            # [Dn, K] bool
    num: np.ndarray                # [Dn, K] f32
    topo_pair: np.ndarray          # [Dn, TK] i32
    taints: np.ndarray             # [Dn, T] bool
    ports: np.ndarray              # [Dn, P] bool
    images: np.ndarray             # [Dn, I] bool
    avoid_hot: np.ndarray          # [Dn, AV] bool
    zone_hot: np.ndarray           # [Dn, Z] f32
    image_size: np.ndarray         # [I] f32 (full replace)
    image_spread: np.ndarray       # [I] f32 (full replace)
    taint_is_hard: np.ndarray      # [T] bool (full replace: a dirty node
                                   # can intern a NEW taint inside the cap)
    taint_is_prefer: np.ndarray    # [T] bool (full replace)
    pod_rows: np.ndarray           # [Dp] i32 (pad = P: dropped)
    pod_kv_ids: np.ndarray         # [Dp, MLp] i32 (densified on device)
    pod_key: np.ndarray            # [Dp, K] bool
    pod_ns_hot: np.ndarray         # [Dp, NS] f32
    pod_node: np.ndarray           # [Dp] i32
    pod_valid: np.ndarray          # [Dp] bool
    pod_terminating: np.ndarray    # [Dp] bool


def gather_delta(host: HostClusterArrays, node_rows: List[int],
                 pod_rows: List[int], pod_floor: int = 8) -> ClusterDelta:
    """Slice the dirty rows out of the host mirror into pow2-bucketed
    update tables (the host half of the delta pipeline).  ``pod_floor``:
    the least pod-row bucket, a power of two (the DeltaTensorizer keeps a
    serving loop's steady churn in one bucket with it)."""
    a = host.arrays
    N = a["allocatable"].shape[0]
    PP = a["pod_node"].shape[0]
    Dn = pow2_bucket(len(node_rows), 8)
    Dp = pow2_bucket(len(pod_rows), pod_floor)
    nr = np.full((Dn,), N, np.int32)
    nr[:len(node_rows)] = node_rows
    pr = np.full((Dp,), PP, np.int32)
    pr[:len(pod_rows)] = pod_rows

    def g(field: str, rows: List[int], cap: int) -> np.ndarray:
        arr = a[field]
        out = np.zeros((cap,) + arr.shape[1:], arr.dtype)
        if rows:
            out[:len(rows)] = arr[rows]
        return out

    return ClusterDelta(
        node_rows=nr,
        allocatable=g("allocatable", node_rows, Dn),
        requested=g("requested", node_rows, Dn),
        nonzero_requested=g("nonzero_requested", node_rows, Dn),
        node_valid=g("node_valid", node_rows, Dn),
        unschedulable=g("unschedulable", node_rows, Dn),
        kv_ids=g("_kv_ids", node_rows, Dn),
        keymask=g("keymask", node_rows, Dn),
        num=g("num", node_rows, Dn),
        topo_pair=g("topo_pair", node_rows, Dn),
        taints=g("taints", node_rows, Dn),
        ports=g("ports", node_rows, Dn),
        images=g("images", node_rows, Dn),
        avoid_hot=g("avoid_hot", node_rows, Dn),
        zone_hot=g("zone_hot", node_rows, Dn),
        image_size=a["image_size"].copy(),
        image_spread=np.asarray(a["image_spread"], np.float32).copy(),
        taint_is_hard=a["taint_is_hard"].copy(),
        taint_is_prefer=a["taint_is_prefer"].copy(),
        pod_rows=pr,
        pod_kv_ids=g("_pod_kv_ids", pod_rows, Dp),
        pod_key=g("pod_key", pod_rows, Dp),
        pod_ns_hot=g("pod_ns_hot", pod_rows, Dp),
        pod_node=g("pod_node", pod_rows, Dp),
        pod_valid=g("pod_valid", pod_rows, Dp),
        pod_terminating=g("pod_terminating", pod_rows, Dp))


def _norm_image(name: str) -> str:
    """Normalize image name: bare names get :latest; a registry-less repo is
    left as-is (reference: imagelocality/image_locality.go normalizedImageName)."""
    if "@" in name:
        return name
    tag_sep = name.rfind(":")
    slash = name.rfind("/")
    if tag_sep <= slash:  # no tag after last path component
        return name + ":latest"
    return name


WILDCARD_IP = "0.0.0.0"
_ANY = "__any__"
_WILD = "__wild__"


def _port_ids_node(triple: Tuple[str, str, int]):
    """Port ids a *node* registers for one used (proto, ip, port).

    Encodes HostPortInfo's wildcard semantics
    (reference: framework/v1alpha1/types.go:694 HostPortInfo.CheckConflict)
    as set-intersection: specific ip registers {specific, ANY}; wildcard
    registers {WILD, ANY}.  A pod checks {specific, WILD} (specific ip) or
    {ANY} (wildcard).  Intersection != 0  <=>  CheckConflict == true.
    """
    proto, ip, port = triple
    if ip == WILDCARD_IP:
        return [(proto, _WILD, port), (proto, _ANY, port)]
    return [(proto, ip, port), (proto, _ANY, port)]


def port_ids_pod(triple: Tuple[str, str, int]):
    """Port ids a *pod* probes for one wanted (proto, ip, port)."""
    proto, ip, port = triple
    if ip == WILDCARD_IP:
        return [(proto, _ANY, port)]
    return [(proto, ip, port), (proto, _WILD, port)]


def _avoid_entries(node: api.Node) -> List[Tuple[str, str]]:
    """(kind, uid) pairs from the preferAvoidPods annotation (reference:
    pkg/apis/core/v1/helper/helpers.go:239 GetAvoidPodsFromNodeAnnotations,
    matched by kind+UID in nodepreferavoidpods/node_prefer_avoid_pods.go:76)."""
    raw = node.metadata.annotations.get(api.PREFER_AVOID_PODS_ANNOTATION_KEY)
    if not raw:
        return []
    import json
    out = []
    try:
        doc = json.loads(raw)
        for entry in doc.get("preferAvoidPods", []):
            ctrl = entry.get("podSignature", {}).get("podController", {})
            out.append((ctrl.get("kind", ""), ctrl.get("uid", "")))
    except (ValueError, AttributeError):
        return []
    return out


def zone_key(node: api.Node) -> str:
    """region:zone key for zone-aware spreading
    (reference: pkg/util/node/node.go:148 GetZoneKey)."""
    labels = node.metadata.labels
    # legacy failure-domain labels take precedence (reference behavior)
    region = labels.get(api.LABEL_REGION_LEGACY, labels.get(api.LABEL_REGION, ""))
    zone = labels.get(api.LABEL_ZONE_LEGACY, labels.get(api.LABEL_ZONE, ""))
    if not region and not zone:
        return ""
    return region + ":\x00:" + zone
