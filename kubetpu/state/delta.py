"""Incremental delta-tensorization: device-resident cluster state updated
by scatter, not rebuilt.

The flight recorder (PR 4) showed the serving host — not the device — is
the drain bottleneck: every non-chained cycle paid a full
``HostClusterArrays.build()`` walk over ALL nodes plus a fresh host→device
transfer, even when the cycle changed a handful of rows.  The
``DeltaTensorizer`` keeps ONE ``ClusterTensors`` alive on device across
cycles and, from the cache's commit/bind/evict/watch churn (per-node
``NodeInfo.generation`` bumps), emits compact ``[D]``-indexed update
tables (``state/tensors.py ClusterDelta``) applied by a donated, jit'd
scatter program (``models/programs.py apply_cluster_delta``,
``x.at[rows].set(..., mode="drop")`` so buffers update in place), bucketed
by ``pow2_bucket(D)`` to avoid recompiles.

The scheduler's gang-mode cycle CHAIN is the zero-delta special case of
this pipeline: the chain covers self-inflicted churn (the auction's own
placements, already materialized on device by ``materialize_assigned``),
while the DeltaTensorizer covers everything else — external binds, node
updates, evictions (including the preemption wave's victim deletions,
which reach it as ordinary cache churn and ride the same delta tables) —
and replaces the full rebuild as the chain-break recovery path.

Full rebuild remains the FALLBACK, demoted to an anti-entropy resync.
Triggers (each counted and reported through ``DeltaStats.reason``):

  * ``initial``             — no resident cluster yet
  * ``node-set``            — nodes added/removed/reordered (row ids move)
  * ``vocab-growth``        — an intern-table pow2 cap crossed (tensor
                              widths change), or the topokey vocab grew at
                              all (``topo_pair`` columns are filled at
                              build time from the key LIST, not the cap)
  * ``label-capacity``      — a node/pod outgrew the compact [., ML] id
                              lists
  * ``delta-too-large``     — dirty fraction above KUBETPU_DELTA_MAX_FRAC
                              (off by default)
  * ``anti-entropy``        — KUBETPU_RESYNC_INTERVAL delta cycles elapsed
  * ``pod-axis-growth``     — pod rows exhausted; the mirror pads to the
                              next pow2 bucket and re-uploads WITHOUT the
                              build() walk (the host-walk cost is the
                              bottleneck, not the transfer)

Term-carrying pod churn is NOT a resync trigger: the two flattened
``ExistingTerms`` tables are kept by ROW (``state/tensors.py TermTable``,
the ``delta-terms`` span): an owner that comes compiles its terms into
free rows, one that goes tombstones its rows, and only the rows written
cross to the device, through a small scatter program of their own
(``models/programs.py apply_terms_delta``, the ``delta-terms-upload``
span).  A table crosses whole only when it changes shape (its rows, its
unique selectors or their requirements outgrow a bucket) or gains a unique
selector.  Plain pods coming and going beside long-lived owners touch
neither table, buffers and all.

A dirty node is one whose ``generation`` moved, which a pod bound to it
or deleted from it does.  Its mirror rows are refilled only where what
they are filled FROM changed: the node's label / taint / image rows when
its Node object was set again (``NodeInfo.node_generation``), a pod's row
when the PodInfo on the node is not the object the row was filled from;
what a pod's coming or going does move — requested, the pod count, host
ports — is rewritten for every dirty node.  A refresh costs what CHANGED,
not what the dirty nodes hold: a node's arrivals and departures are the
difference of two sets of PodInfo objects (``node_pods``), so no Python
statement runs for a pod that stayed, and the ``ClusterDelta`` carries
every dirty node's row but only the pod rows that were refilled or
cleared — a row that was not is byte for byte what the device holds.  So
that the steady churn
of a serving loop (a batch of arrivals, about as many departures) and its
jitter land in ONE compiled scatter, the delta's pod rows are padded to
at least four times the pending batch's pow2 bucket (``refresh``).

Bit-exactness contract (tested by tests/test_delta.py): after any
sequence of deltas, the resident tensors match a from-scratch ``build()``
of the same NodeInfos against the same InternTable byte-for-byte, up to
the documented stable-row permutation of the existing-pod axis (a fresh
build packs pods in node-walk order; the delta path keeps rows stable and
reuses freed rows lowest-first).  The two term tables hold the same
MULTISET of valid rows as the build's, each row read through its
selector's requirement content and its owner's uid, and every other row
is a padding row (a build packs them in node-walk order; the delta path
keeps an owner's rows where they are).  Known deviation: when several nodes
report the SAME image with DIFFERENT sizes, build() keeps the last walked
node's size while the delta path keeps the last updated node's.
"""

from __future__ import annotations

import os
import pickle
from types import MappingProxyType
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple

import numpy as np

from ..utils import journal as ujournal
from ..utils.intern import pow2_bucket
from ..utils.trace import wallclock
from .tensors import (TERM_KINDS, ClusterDelta, HostClusterArrays,
                      SnapshotBuilder, TermTable, clear_pod_row,
                      fill_node_static, fill_node_usage, fill_pod_row,
                      gather_delta, gather_terms_delta, pod_has_terms,
                      vocab_signature)

RESYNC_INTERVAL_ENV = "KUBETPU_RESYNC_INTERVAL"
MAX_FRAC_ENV = "KUBETPU_DELTA_MAX_FRAC"
# anti-entropy VERIFIER cadence (delta cycles between device/mirror
# fingerprint checks); 0 = off, the default — a disarmed run performs
# zero extra readbacks (the chaos poison test enforces it)
VERIFY_INTERVAL_ENV = "KUBETPU_VERIFY_INTERVAL"
DEFAULT_RESYNC_INTERVAL = 512
# dirty-fraction fallback is OFF by default (1.0 = never): even a
# fully-dirty delta beats a rebuild — the refill walk is the same
# per-node work, but it skips the intern pass, the term rebuild, the
# fresh array allocation and most of the transfer.  Operators can lower
# it (KUBETPU_DELTA_MAX_FRAC=0.5) if a workload proves otherwise.
DEFAULT_MAX_FRAC = 1.0

# pod-axis mirror fields padded on growth (pad value per field)
_POD_FIELDS = (("_pod_kv_ids", -1), ("pod_key", False), ("pod_ns_hot", 0.0),
               ("pod_node", -1), ("pod_valid", False),
               ("pod_terminating", False))

# fields excluded from the anti-entropy fingerprint: the dense label
# one-hots exist ONLY on device (the mirror holds compact [., ML] id
# lists and to_device densifies — state/tensors.py), so there is no
# cheap host twin to sum against.  Their source id lists feed pod_key /
# keymask / topo_pair, which ARE fingerprinted, so label-scatter faults
# still surface; the documented blind spot is a corruption of the dense
# kv bits alone.
_FP_SKIP = ("kv", "pod_kv")


def _wrapsum_host(x: np.ndarray) -> int:
    """uint32 wrap-sum of a mirror array's element bits: bools count set
    bits, floats sum their f32 bit patterns, ints sum mod 2^32 — the
    exact integer twin of _wrapsum_dev (no float accumulation anywhere,
    so the comparison is bit-exact at any size)."""
    x = np.asarray(x)
    if x.dtype == np.bool_:
        v = x.astype(np.uint32)
    elif np.issubdtype(x.dtype, np.floating):
        v = np.ascontiguousarray(x.astype(np.float32)).view(np.uint32)
    else:
        v = x.astype(np.uint32)
    return int(v.sum(dtype=np.uint64) & 0xFFFFFFFF)


def _wrapsum_dev(x):
    """Device twin of _wrapsum_host: a [""] uint32 scalar, computed with
    EAGER ops (not a jit root — the verifier must not widen the census
    compile surface; it runs off the hot path on a cadence)."""
    import jax.numpy as jnp
    from jax import lax
    if x.dtype == jnp.bool_:
        v = x.astype(jnp.uint32)
    elif jnp.issubdtype(x.dtype, jnp.floating):
        v = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    else:
        v = x.astype(jnp.uint32)
    return jnp.sum(v, dtype=jnp.uint32)


class DeltaStats(NamedTuple):
    """One refresh()'s outcome — the flight recorder's feed."""
    delta_rows: int                 # dirty node rows + the pod rows that
                                    # were refilled or cleared
    resync: bool
    reason: str                     # "" on pure delta cycles
    spans: Tuple[Tuple[str, float, float], ...]  # (name, t0, t1)
    # the (dirty-node, churned-pod) row buckets _apply_cluster_delta was
    # dispatched with (gather_delta's pow2 pads, the pod rows' from
    # refresh()'s floor up): the program compiles once per pair.  () when
    # no scatter ran
    delta_buckets: Tuple[int, ...] = ()
    # span name -> the args it is recorded with besides delta_rows: what
    # the term update did ("delta-terms": filter_rows, score_rows, the
    # tables' LIVE rows; their buckets Et / Es; owners_changed, the owners
    # that came, went or were replaced; rows_written, rows tombstoned plus
    # rows appended; rows_free; wholesale, 1 where a table crossed to the
    # device whole; pods_walked as below) and what the build did
    # ("delta-build": terms_kept, whether the tables hold live rows and
    # none was written; node_rows_dirty / node_rows_refilled;
    # pod_rows_seen, the pod rows in the delta: refilled or cleared;
    # pod_rows_refilled; pods_walked, the dirty nodes' pods the host
    # visited one at a time: the arrivals)
    span_args: Mapping[str, Mapping[str, int]] = MappingProxyType({})


class TermOwner(NamedTuple):
    """Everything one existing pod gives the two term tables: its stable
    delta row (``pod_idx``) and the parsed term lists its rows compile
    from, under PodInfo's names (``owner_terms`` reads an owner through
    them).  Compared by VALUE with the lists themselves held, so a pod
    replaced in place under its uid keeps its term rows where its row and
    the content of its terms stayed, and is an owner gone and an owner
    come where either changed."""
    uid: str
    row: int
    required_anti_affinity_terms: list
    preferred_affinity_terms: list
    preferred_anti_affinity_terms: list
    required_affinity_terms: list


class DeltaTensorizer:
    """Keeps ClusterTensors resident on device and updates them by
    bounded scatters from the cycle's cache churn.

    Owned by the serving thread (like the scheduler's chain); the host
    mirror (``HostClusterArrays``) is the source of truth the device
    tensors always equal, and a resync re-derives everything from the
    snapshot.  ``mesh`` keeps the resident cluster SHARDED so sharded
    profiles stop re-``device_put``-ing the whole [N, R] tensors — the
    replicated delta tables scatter into the local shards
    (parallel/mesh.py sharded_apply_cluster_delta).
    """

    def __init__(self, hard_pod_affinity_weight: int = 1, mesh=None,
                 resync_interval: Optional[int] = None,
                 max_delta_frac: Optional[float] = None,
                 verify_interval: Optional[int] = None):
        self.builder = SnapshotBuilder(
            hard_pod_affinity_weight=hard_pod_affinity_weight)
        self.hard_pod_affinity_weight = hard_pod_affinity_weight
        self.mesh = mesh
        self.resync_interval = (resync_interval if resync_interval is not None
                                else int(os.environ.get(
                                    RESYNC_INTERVAL_ENV,
                                    str(DEFAULT_RESYNC_INTERVAL))))
        self.max_delta_frac = (max_delta_frac if max_delta_frac is not None
                               else float(os.environ.get(
                                   MAX_FRAC_ENV, str(DEFAULT_MAX_FRAC))))
        self.cluster = None                      # device ClusterTensors
        self.host: Optional[HostClusterArrays] = None
        self.node_names: List[str] = []          # row order
        self.node_gen: Dict[str, int] = {}
        # name -> the PodInfo OBJECTS the node's pod rows were filled
        # from (the snapshot's clone() keeps the objects and every pod
        # event puts a new one on its node): what came and went on a dirty
        # node is the difference of two such sets
        self.node_pods: Dict[str, set] = {}
        # the two term tables' rows by owner ("filter_terms" /
        # "score_terms" -> TermTable over the mirror), set by _resync
        self.term_tables: Dict[str, TermTable] = {}
        self.pod_row: Dict[str, int] = {}        # uid -> row
        # what each mirror row was last filled FROM, so that refresh()
        # refills a row only when that changed.  name -> the
        # ``NodeInfo.node_generation`` the node's static rows
        # (``fill_node_static``) were filled at; uid -> (the PodInfo the
        # pod's row was filled from, its node's row, its TermOwner or
        # None).  Held by OBJECT, not by id(): a PodInfo that is still
        # noted cannot have been collected and its address reused.
        # Noted as a row is assigned or kept for a refill and re-noted
        # whole by every _resync (which moves pod rows and intern ids)
        self.node_src: Dict[str, int] = {}
        self.pod_src: Dict[str, Tuple[object, int,
                                      Optional[TermOwner]]] = {}
        self.free_rows: List[int] = []           # kept sorted, pop lowest
        # row -> uid (None: free or padding), sized to the pod-axis
        # capacity and kept up to date wherever a row is assigned or
        # freed: ``pod_row`` the other way round
        self.row_uids: List[Optional[str]] = []
        self.next_pod_row = 0
        self.caps = None                         # vocab signature
        self.cycles_since_resync = 0
        self.resync_count = 0
        # anti-entropy verifier (fingerprint_device vs fingerprint_host
        # every verify_interval delta cycles; 0 = off)
        self.verify_interval = (verify_interval
                                if verify_interval is not None
                                else int(os.environ.get(
                                    VERIFY_INTERVAL_ENV, "0")))
        self.cycles_since_verify = 0
        self.verify_count = 0
        self.divergence_count = 0
        # cycle-journal capture seam (utils/journal.py): when the journal
        # is armed, each refresh() stashes the exact input it applied to
        # the resident cluster — ("resync", pickled mirror) on any full
        # rebuild/re-upload, ("delta", pickled (ClusterDelta, terms)) on
        # a scatter cycle (terms: None, or what _apply_terms put on the
        # device), ("noop", None) on zero-dirty cycles — and the
        # scheduler pops it into the cycle's journal record
        # (take_capture).  Disarmed this stays None: zero allocations.
        self.capture = None

    def take_capture(self):
        """Pop the last refresh()'s journal capture (None when the
        journal is disarmed — the seam costs one attribute read)."""
        cap, self.capture = self.capture, None
        return cap

    def _capture_resync(self) -> None:
        """Serialize the freshly-uploaded mirror as a journal anchor
        (armed only).  Pickled EAGERLY: later refreshes mutate the
        mirror arrays in place, so a lazy reference would record the
        wrong snapshot."""
        if ujournal.journal() is not None:
            self.capture = ("resync", pickle.dumps(self.host, protocol=4))

    # ------------------------------------------------------------- helpers

    def signature(self) -> tuple:
        """The tensor-width signature of the current vocab (shared with
        the scheduler's chain guard — state/tensors.vocab_signature)."""
        return vocab_signature(self.builder.table)

    def safe_to_donate(self, uncommitted_clusters) -> bool:
        """Donation gate for the depth-k pipelined drain: the donated
        scatter may only consume the resident buffers when NO
        dispatched-but-uncommitted cycle's cluster IS the resident —
        every in-flight ring slot's commit-side device work (preemption
        wave, decision audit) still dispatches against its cluster, and
        a donated buffer would be invalid by then.  Chained cycles hold
        their own materialized clusters and never block donation."""
        return not any(c is self.cluster for c in uncommitted_clusters)

    def pod_uid_list(self) -> List[Optional[str]]:
        """Row-ordered uid list sized to the pod-axis capacity (the
        scheduler's chain_pod_uids / CycleContext.pod_uids feed).  A
        COPY: the next refresh moves the tensorizer's own."""
        return list(self.row_uids)

    # ------------------------------------------------------- anti-entropy

    def fingerprint_device(self) -> np.ndarray:
        """[K] uint32 per-table wrap-sums of the DEVICE residents — one
        small readback (the eager per-leaf sums stack into one array and
        transfer together)."""
        import jax
        import jax.numpy as jnp
        vals = []
        for name in type(self.cluster)._fields:
            if name in _FP_SKIP:
                continue
            for leaf in jax.tree.leaves(getattr(self.cluster, name)):
                vals.append(_wrapsum_dev(leaf))
        return np.asarray(jnp.stack(vals))

    def fingerprint_host(self) -> np.ndarray:
        """The host mirror's twin of fingerprint_device, same leaf order
        (ClusterTensors field order; term pytrees flatten identically)."""
        import jax
        a = self.host.arrays
        vals = []
        for name in type(self.cluster)._fields:
            if name in _FP_SKIP:
                continue
            for leaf in jax.tree.leaves(a[name]):
                vals.append(_wrapsum_host(leaf))
        return np.asarray(vals, np.uint32)

    def verify(self) -> bool:
        """One anti-entropy check: True when the device residents match
        the host mirror bit-for-bit under the per-table fingerprint."""
        ok = bool(np.array_equal(self.fingerprint_device(),
                                 self.fingerprint_host()))
        self.verify_count += 1
        if not ok:
            self.divergence_count += 1
        return ok

    def _verify_tick(self, node_infos, names, pending):
        """Cadence gate around verify(): returns (spans, stats) where
        spans carries the verify span when a check ran and stats is the
        divergence-triggered resync's DeltaStats (reason
        "verify-divergence") or None when consistent / not due.  OFF
        (verify_interval == 0, the default) this is two attribute reads
        — no device work, no readback."""
        if not self.verify_interval or self.cluster is None:
            return (), None
        self.cycles_since_verify += 1
        if self.cycles_since_verify < self.verify_interval:
            return (), None
        self.cycles_since_verify = 0
        tv = wallclock()
        ok = self.verify()
        span = (("verify", tv, wallclock()),)
        if ok:
            return span, None
        # divergence: the mirror is the source of truth (refilled from
        # NodeInfos each cycle), so the targeted repair is the blessed
        # full resync — re-derives and re-uploads everything
        _cluster, stats = self._resync(node_infos, names,
                                       "verify-divergence", wallclock(),
                                       pending)
        return span, stats._replace(spans=span + stats.spans)

    # ------------------------------------------------------------- refresh

    def refresh(self, node_infos, pending=(), donate: bool = True,
                batch: Optional[int] = None):
        """Bring the resident cluster up to date with the snapshot's
        NodeInfos.  Returns (cluster, DeltaStats).  pending: PodInfos of
        this cycle's pending (and nominated) pods — interned HERE so the
        vocab-growth check always sees them (and so a compacting resync
        re-interns them into its fresh table).  donate=False keeps the
        previous device buffers alive (an in-flight pipelined cycle still
        reads them).  batch: how many of ``pending`` are the cycle's batch
        (all of them unless said: the scheduler hands the nominated pods
        in behind it); the delta's pod-row floor stands on it alone."""
        t0 = wallclock()
        if pending:
            self.builder.intern_pending(pending)
        names = [ni.node.metadata.name if ni.node is not None else ""
                 for ni in node_infos]
        if self.cluster is None:
            return self._resync(node_infos, names, "initial", t0, pending)
        if names != self.node_names:
            return self._resync(node_infos, names, "node-set", t0, pending)
        # BEFORE the zero-dirty early return: pending/nominated pods can
        # grow the vocab with zero node churn, and serving the resident
        # tensors then would hand the program stale widths (or an all- -1
        # topo_pair column for a brand-new topology key)
        if self.signature() != self.caps:
            return self._resync(node_infos, names, "vocab-growth", t0,
                                pending)
        if self.cycles_since_resync >= self.resync_interval:
            return self._resync(node_infos, names, "anti-entropy", t0,
                                pending)
        node_gen = self.node_gen
        dirty = [(i, ni) for i, ni in enumerate(node_infos)
                 if ni.generation != node_gen.get(names[i])]
        if not dirty:
            self.cycles_since_resync += 1
            if ujournal.journal() is not None:
                # zero-dirty: the journal records "previous cluster, as
                # is" (a verify-divergence resync below overwrites this)
                self.capture = ("noop", None)
            # the verifier ticks on zero-dirty cycles too: a corruption
            # injected by the LAST scatter must not hide behind a quiet
            # cluster until the next churn
            vspan, vstats = self._verify_tick(node_infos, names, pending)
            if vstats is not None:
                return self.cluster, vstats
            return self.cluster, DeltaStats(0, False, "", vspan)
        if len(dirty) > self.max_delta_frac * max(len(names), 1):
            return self._resync(node_infos, names, "delta-too-large", t0,
                                pending)

        # A mirror row is refilled only when what it is filled FROM
        # changed.  The node's static rows are filled from the Node
        # object: ``NodeInfo.node_generation`` moves when it is set
        # again (an in-place edit re-set with update_node(n, n)
        # included) and not when a pod comes or goes.  A pod's row is
        # filled from its PodInfo and its node's row: every pod event
        # puts a NEW PodInfo on the node (``SchedulerCache.update_pod``
        # is a remove and an add) and the snapshot's ``clone()`` keeps
        # the objects, so a PodInfo that is still in the node's noted set
        # (``node_pods``; node rows do not move between resyncs) means
        # the row is what a refill would write.  Strings are interned
        # with the part that reads them, so the width checks below see
        # exactly the strings that are new.  Any _resync() from here on
        # finds state half updated and re-derives ALL of it, markers
        # included.
        a = self.host.arrays
        b = self.builder
        pod_row, pod_src = self.pod_row, self.pod_src
        node_pods, node_src = self.node_pods, self.node_src
        row_uids = self.row_uids

        # ---- what came and went on each dirty node: the difference of
        # two sets of PodInfo objects, so nothing runs a pod that stayed.
        # EVERY departed row across all dirty nodes is freed BEFORE a row
        # is assigned — a same-uid pod moving from a higher- to a
        # lower-indexed dirty node would otherwise keep its stale mapping
        # through its arrival and lose it to the later free, leaving the
        # refill with no row.  A pod replaced on its node under its uid
        # (update_pod's remove and add) keeps its row: it is refilled
        touched_pods: set = set()
        owners_went: List[str] = []     # uids whose term rows go
        owners_came: List[TermOwner] = []
        walk = []       # (node row, NodeInfo, arrivals)
        for i, ni in dirty:
            name = names[i]
            pods = ni.pods
            now = set(pods)
            was = node_pods[name]
            came = now - was
            if len(came) > 1:
                # rows are assigned in pod order, not in a set's
                came = sorted(came, key=pods.index)
            went = was - now
            if went:
                stays = {pi.pod.metadata.uid for pi in came}
                for pi in went:
                    uid = pi.pod.metadata.uid
                    if uid in stays:
                        continue
                    row = pod_row.pop(uid)
                    if pod_src.pop(uid)[2] is not None:
                        owners_went.append(uid)
                    clear_pod_row(a, row)
                    row_uids[row] = None
                    touched_pods.add(row)
                    self.free_rows.append(row)
            node_pods[name] = now
            walk.append((i, ni, came))
        self.free_rows.sort()

        # ---- the arrivals of each dirty node: a row for each that has
        # none (lowest free row first, in walk order), the rows to
        # (re)fill, and the term owners among them
        MLn = a["_kv_ids"].shape[1]
        MLp = a["_pod_kv_ids"].shape[1]
        free, n_free, k_free = self.free_rows, len(self.free_rows), 0
        reset_nodes: List[Tuple[int, object]] = []
        fills: List[Tuple[int, object, int]] = []   # (row, PodInfo, node row)
        # term-carrying pod churn does NOT force a full resync: an owner
        # that comes or goes is noted here and its rows are written below
        # (_update_terms).  ``pod_src`` says whether a pod that went was
        # an owner; one replaced in place under its uid is an owner gone
        # and an owner come unless its row and its terms' content stayed
        # (TermOwner compares by value).  The anti-entropy verifier cannot
        # catch a row kept wrongly: it compares the device with the
        # mirror, and both would be stale together — tests/test_delta.py
        # holds the kept tables to a fresh build()
        pods_walked = 0
        for i, ni, came in walk:
            name = names[i]
            if ni.node_generation != node_src.get(name):
                if len(ni.node.metadata.labels) + 1 > MLn:
                    return self._resync(node_infos, names,
                                        "label-capacity", t0, pending)
                b.intern_node(ni)
                reset_nodes.append((i, ni))
            b.intern_node_usage(ni)
            pods_walked += len(came)
            for pi in came:
                if len(pi.pod.metadata.labels) > MLp:
                    return self._resync(node_infos, names,
                                        "label-capacity", t0, pending)
                b.intern_pod(pi)
                uid = pi.pod.metadata.uid
                row = pod_row.get(uid)
                if row is None:
                    if k_free < n_free:
                        row = free[k_free]
                        k_free += 1
                    else:
                        row = self.next_pod_row
                        self.next_pod_row += 1
                    pod_row[uid] = row
                    if row < len(row_uids):
                        row_uids[row] = uid
                    else:
                        # past the capacity the list grows a row at a
                        # time; _grow_pod_axis pads it to the new bucket
                        row_uids.append(uid)
                owner = self._owner(uid, row, pi)
                was = pod_src.get(uid)
                if was is None or was[2] != owner:
                    if was is not None and was[2] is not None:
                        owners_went.append(uid)
                    if owner is not None:
                        owners_came.append(owner)
                pod_src[uid] = (pi, i, owner)
                fills.append((row, pi, i))
                touched_pods.add(row)
        del free[:k_free]
        # AFTER the interning, BEFORE any fill: new strings from what is
        # about to be filled count against the caps the resident tensors
        # were sized with
        if self.signature() != self.caps:
            return self._resync(node_infos, names, "vocab-growth", t0,
                                pending)
        grown = self.next_pod_row > a["pod_node"].shape[0]
        if grown:
            self._grow_pod_axis(self.next_pod_row)

        # ---- refill the mirror rows whose source changed
        t = b.table
        if reset_nodes:
            # a Node set again can have interned a NEW taint inside the
            # cap: the [T] vocab-metadata rows for fresh ids must land
            # too (build() fills them from the vocab; ids are
            # append-only, so only the tail can be stale)
            from ..api import types as api
            for ti in range(len(t.taint)):
                if (not a["taint_is_hard"][ti]
                        and not a["taint_is_prefer"][ti]):
                    _, _, effect = t.taint.key(ti)
                    a["taint_is_hard"][ti] = effect in (
                        api.TAINT_EFFECT_NO_SCHEDULE,
                        api.TAINT_EFFECT_NO_EXECUTE)
                    a["taint_is_prefer"][ti] = (
                        effect == api.TAINT_EFFECT_PREFER_NO_SCHEDULE)
            image_nodes = a["_image_nodes"]
            for i, ni in reset_nodes:
                old_imgs = set(np.nonzero(a["images"][i])[0].tolist())
                fill_node_static(a, i, ni, t)
                new_imgs = set(np.nonzero(a["images"][i])[0].tolist())
                for ii in old_imgs - new_imgs:
                    image_nodes[ii] -= 1
                for ii in new_imgs - old_imgs:
                    image_nodes[ii] += 1
                node_src[names[i]] = ni.node_generation
            # images that no node carries anymore read 0 in a fresh build
            a["image_size"][image_nodes <= 0] = 0.0
            a["image_spread"] = image_nodes / max(float(len(node_infos)),
                                                  1.0)
        for row, pi, i in fills:
            fill_pod_row(a, row, pi, i, t)
        node_rows = []
        for i, ni in dirty:
            fill_node_usage(a, i, ni, t)
            node_gen[names[i]] = ni.generation
            node_rows.append(i)

        terms_dirty = bool(owners_went or owners_came)
        term_span = ()
        term_rows = None
        span_args: Dict[str, Dict[str, int]] = {"delta-build": {
            "terms_kept": int(not terms_dirty and any(
                tt.live for tt in self.term_tables.values())),
            "node_rows_dirty": len(dirty),
            "node_rows_refilled": len(reset_nodes),
            "pod_rows_seen": len(touched_pods),
            "pod_rows_refilled": len(fills),
            "pods_walked": pods_walked}}
        if terms_dirty:
            t_terms = wallclock()
            term_rows, said = self._update_terms(owners_went, owners_came)
            # a selector compiled just now interns the values it names: a
            # cap they crossed is the resync it is at the top of refresh()
            if self.signature() != self.caps:
                return self._resync(node_infos, names, "vocab-growth", t0,
                                    pending)
            span_args["delta-terms"] = dict(said, pods_walked=pods_walked)
            term_span = (("delta-terms", t_terms, wallclock()),)

        pod_rows = sorted(touched_pods)
        if grown:
            # the pod axis changed shape: scatter can't grow a buffer, so
            # re-upload the (already-updated) mirror — no build() walk.
            # The markers stand as the fills above noted them: padding
            # moves no row and this table's ids stay
            self.cycles_since_resync = 0
            self.resync_count += 1
            t_build = wallclock()
            self._upload()
            self._capture_resync()
            return self.cluster, DeltaStats(
                len(node_rows) + len(pod_rows), True, "pod-axis-growth",
                (("delta-build", t0, t_build),) + term_span
                + (("resync", t_build, wallclock()),), span_args=span_args)
        # ONE size for the steady state's pod rows: a serving loop's
        # refresh sees about the last batch's arrivals and as many
        # departures, up to twice the batch's bucket and wandering across
        # that pow2 edge; four times the batch's bucket (the batch
        # builder's, models/batch.py; the nominated pods behind the batch
        # are no part of it: one of them must not double the floor in the
        # middle of a preemption wave) holds that count and its jitter in
        # one bucket, and more rows take the next as before
        if batch is None:
            batch = len(pending)
        floor = 4 * pow2_bucket(batch, 8)
        delta = gather_delta(self.host, node_rows, pod_rows, pod_floor=floor)
        t_build = wallclock()
        upload_span: list = []
        self.cluster = self._apply(delta, donate=donate,
                                   term_rows=term_rows, floor=floor,
                                   spans=upload_span)
        self.cycles_since_resync += 1
        spans = ((("delta-build", t0, t_build),) + term_span
                 + tuple(upload_span)
                 + (("delta-apply", t_build, wallclock()),))
        buckets = (int(delta.node_rows.shape[0]),
                   int(delta.pod_rows.shape[0]))
        vspan, vstats = self._verify_tick(node_infos, names, pending)
        if vstats is not None:
            return self.cluster, vstats._replace(spans=spans
                                                 + vstats.spans,
                                                 delta_buckets=buckets,
                                                 span_args=span_args)
        return self.cluster, DeltaStats(
            len(node_rows) + len(pod_rows), False, "", spans + vspan,
            buckets, span_args)

    # ------------------------------------------------------------- resync

    def _resync(self, node_infos, names: List[str], reason: str,
                t0: float, pending=()):
        """The blessed full rebuild: anti-entropy resync + every fallback
        trigger.  Also the vocab COMPACTION point: everything re-derives
        here, so intern ids are free to move and the table restarts FRESH
        — without this, dead label values (pod-template-hash churn across
        rollouts) would grow the vocab, and so the resident tensor
        widths, without bound.  Ids only need stability BETWEEN resyncs
        (the delta path's contract).  pending: this cycle's pending/
        nominated PodInfos, re-interned into the fresh table before
        sizing so batch tensors and cluster tensors agree on widths."""
        self.builder = SnapshotBuilder(
            hard_pod_affinity_weight=self.hard_pod_affinity_weight)
        if pending:
            self.builder.intern_pending(pending)
        host = self.builder.build(node_infos)
        a = host.arrays
        self.host = host
        self.node_names = list(names)
        self.pod_row = dict(a["_pod_rows"])
        self.next_pod_row = len(self.pod_row)
        self.free_rows = []
        # every marker is noted again, for every reason this is called
        # with: the rebuild packed the pod rows anew (an owner carries
        # its row) and, compacting, moved the intern ids
        self.node_gen, self.node_src = {}, {}
        self.node_pods, self.pod_src = {}, {}
        # the build packed both term tables in node-walk order: no
        # tombstone, no selector that no owner names, and the owner ->
        # rows maps are what it built
        self.term_tables = {field: TermTable(self.builder, a, field)
                            for field in TERM_KINDS}
        self.row_uids = [None] * a["pod_node"].shape[0]
        for i, (name, ni) in enumerate(zip(names, node_infos)):
            for pi in ni.pods:
                uid = pi.pod.metadata.uid
                row = self.pod_row.get(uid)
                if row is None:
                    continue         # build() gives a node-less info no row
                self.row_uids[row] = uid
                self.pod_src[uid] = (pi, i, self._owner(uid, row, pi))
            self.node_gen[name] = ni.generation
            self.node_src[name] = ni.node_generation
            self.node_pods[name] = set(ni.pods)
        self.caps = self.signature()
        self.cycles_since_resync = 0
        # a resync re-uploads the mirror wholesale, so device == mirror
        # by construction; restart the verify cadence
        self.cycles_since_verify = 0
        self.resync_count += 1
        self._upload()
        self._capture_resync()
        return self.cluster, DeltaStats(
            0, True, reason, (("resync", t0, wallclock()),))

    def _grow_pod_axis(self, needed: int) -> None:
        """Pad the mirror's pod-axis arrays to the next pow2 bucket —
        freed-row reuse keeps rows stable, so growth only appends
        padding rows identical to a fresh build's."""
        a = self.host.arrays
        PP = a["pod_node"].shape[0]
        new_pp = pow2_bucket(needed, 8)
        n = new_pp - PP
        if n <= 0:
            return
        for field, fill in _POD_FIELDS:
            arr = a[field]
            pad = np.full((n,) + arr.shape[1:], fill, arr.dtype)
            a[field] = np.concatenate([arr, pad])
        self.row_uids.extend([None] * (new_pp - len(self.row_uids)))

    def _upload(self) -> None:
        """Full host→device transfer of the mirror (resync / pod-axis
        growth); sharded when a mesh is configured so the resident
        tensors live pre-sharded across cycles."""
        cluster = self.host.to_device()
        if self.mesh is not None:
            from ..parallel import mesh as pmesh
            cluster = pmesh.shard_cluster(cluster, self.mesh)
        self.cluster = cluster

    def _owner(self, uid: str, row: int, pi) -> Optional[TermOwner]:
        """What the pod gives the term tables, or None for a pod that
        gives them no row (required affinity owns one only at a nonzero
        hard weight — ``pod_has_terms``)."""
        if not pod_has_terms(pi, self.hard_pod_affinity_weight):
            return None
        return TermOwner(uid, row, pi.required_anti_affinity_terms,
                         pi.preferred_affinity_terms,
                         pi.preferred_anti_affinity_terms,
                         pi.required_affinity_terms)

    def _update_terms(self, went: List[str], came: List[TermOwner]):
        """Write one cycle's owner churn into the mirror's two term
        tables (TermTable.update: ``went``'s rows tombstoned, ``came``'s
        terms compiled into free rows).  Returns (field -> the rows
        written, None for a table that has to cross whole; the
        ``delta-terms`` span's args, refresh() adds ``pods_walked``).

        Why nothing else moves a row between resyncs: a row reads its
        selector as kv / key ids (``fill_unique`` interns what it meets
        and ids are append-only), its namespaces and topology key as ids
        interned WITH the owner (``intern_pod`` as its row is filled),
        ``pod_idx`` as the owner's delta row (stable while the uid stays
        on its node; a move frees and re-assigns it, and is an owner gone
        and an owner come) and its weight (the term's own, or the
        tensorizer's constant hard weight).  The widths are vocab CAPS,
        never lengths, and a cap crossing is a resync.  Nothing reads the
        owner's node (the match looks ``pod_node[pod_idx]`` up on the
        device), another pod, or a node's labels."""
        ft, st = (self.term_tables[f] for f in TERM_KINDS)
        rows, written = {}, 0
        for tt in (ft, st):
            rows[tt.field], n = tt.update(went, came)
            written += n
        return rows, {
            "owners_changed": len(set(went).union(o.uid for o in came)),
            "filter_rows": ft.live, "score_rows": st.live,
            "Et": int(ft.terms.valid.shape[0]),
            "Es": int(st.terms.valid.shape[0]),
            "rows_written": written,
            "rows_free": len(ft.free) + len(st.free),
            "wholesale": int(any(r is None for r in rows.values()))}

    def _device_terms(self, field: str):
        """One of the mirror's term tables as device (mesh: replicated)
        arrays, for a table that crosses whole."""
        import jax
        import jax.numpy as jnp
        # jnp.array, not asarray: these leaves join the DONATED cluster
        # (see HostClusterArrays.to_device) — an aliased mirror buffer
        # would be clobbered by the scatter's buffer reuse
        terms = jax.tree.map(jnp.array, self.host.arrays[field])
        if self.mesh is not None:
            from ..parallel import mesh as pmesh
            terms = pmesh.replicate(terms, self.mesh)
        return terms

    def _apply_terms(self, cluster, term_rows, floor: int, donate: bool):
        """Bring the resident term tables up to the mirror's: a table
        whose shape or unique selectors changed (rows None) crosses whole,
        and the rows written to the others are gathered into TermsDeltas
        of at least ``floor`` rows each and scattered by ONE program, so a
        steady drain runs one variant of it.  Returns (cluster, what went
        to the device: the journal's capture of the cycle's terms)."""
        from ..models import programs
        a = self.host.arrays
        whole = {f: a[f] for f, rows in term_rows.items() if rows is None}
        if whole:
            cluster = cluster._replace(
                **{f: self._device_terms(f) for f in whole})
        # (filter, score), as apply_terms_delta takes them; a table that
        # crossed whole has no row left to send
        deltas = tuple(
            gather_terms_delta(a[f], () if f in whole else term_rows[f],
                               floor)
            for f in TERM_KINDS)
        dev = deltas
        if self.mesh is not None:
            from ..parallel import mesh as pmesh
            dev = pmesh.replicate(deltas, self.mesh)
        ft, st = programs.apply_terms_delta(
            cluster.filter_terms, cluster.score_terms, *dev, donate=donate)
        return (cluster._replace(filter_terms=ft, score_terms=st),
                (whole, deltas))

    def _apply(self, delta: ClusterDelta, donate: bool, term_rows=None,
               floor: int = 8, spans: Optional[list] = None):
        """``term_rows``: what _update_terms wrote, where an owner came
        or went.  ``spans``: where given, the ``delta-terms-upload`` span
        around the transfer and dispatch of the term rows is appended to
        it."""
        from ..models import programs
        from ..utils import chaos
        cluster = self.cluster
        terms = None
        if term_rows is not None:
            # BEFORE the cluster's own scatter: that program passes the
            # term tables through, donated
            t_up = wallclock()
            cluster, terms = self._apply_terms(cluster, term_rows, floor,
                                               donate)
            if spans is not None:
                spans.append(("delta-terms-upload", t_up, wallclock()))
        if ujournal.journal() is not None:
            # journal capture: the exact scatter tables (and the term
            # rows, with any table that crossed whole) this cycle applies
            # — pickled eagerly, the mirror a whole table aliases mutates
            # in place next cycle.
            # Captured BEFORE the chaos seam below: the journal records
            # applied INTENT, so a chaos-dropped scatter replays as a
            # detectable divergence (the fault class the replay rig
            # exists to expose)
            self.capture = ("delta", pickle.dumps((delta, terms),
                                                  protocol=4))
        # chaos seam (utils/chaos.py "delta"): "drop" loses the scatter
        # entirely (the mirror was already refilled, so device and host
        # now silently diverge — the exact fault class the anti-entropy
        # verifier exists to catch); "corrupt" applies the scatter, then
        # flips one resident value the way a bad DMA would
        act = chaos.action("delta")
        if act == "drop":
            return cluster
        if self.mesh is not None:
            from ..parallel import mesh as pmesh
            new = pmesh.sharded_apply_cluster_delta(
                cluster, delta, self.mesh, donate=donate)
        else:
            new = programs.apply_cluster_delta(cluster, delta,
                                               donate=donate)
        if act == "corrupt":
            new = new._replace(requested=new.requested.at[0, 0].add(1.0))
        return new
