"""Device-side observability: measured per-program device time, the HBM
residency ledger, and roofline attribution.

Every device-side number the stack reported before this module was
``device_wait_s`` — host wall-clock blocked on the packed readback —
which the depth-k pipeline deliberately hides: overlap makes the number
a lie (bench.py refused to compute achieved TFLOP/s on pipelined runs
for exactly that reason), and both ROADMAP north-star items terminate in
claims ("<1 s p99 at 100k x 10k", "Mosaic kernel: device time down")
that could not be attributed to the device at all.  Three pillars:

1. MEASURED PER-PROGRAM DEVICE TIME.  A sampled deep-timing mode fences
   individual dispatches: every Nth cycle (``KUBETPU_DEVSTATS_SAMPLE``,
   default 8) the scheduler reads back one SMALL output right after
   each program dispatch (np.asarray — the same completion signal the
   serving path's packed readback is; whether ``jax.block_until_ready``
   should replace it is ROADMAP C6, see the SYNC_PROBE note in
   scheduler.py ``_readback_group``) and records
   the wall seconds as that program's
   ``device_time_s`` (programs: ``run_auction``,
   ``schedule_sequential``, ``apply_cluster_delta``;
   ``explain_verdicts`` is recorded on EVERY armed failure cycle — its
   ``np.asarray`` readback is already a natural sync, so the
   measurement is free).  The fence serializes work the pipeline would
   have overlapped, so sampling bounds the overhead to ~1/N of cycles
   and the cumulative fenced seconds are recorded
   (``fence_wait_s``) so the overhead is never invisible.  Where the
   ``jax.profiler`` capture hook runs (``trace.capture_device_trace``),
   ``ingest_xplane`` additionally parses the XPlane capture into
   per-program records when the profiler tooling is importable, and
   records WHY not when it isn't — never silently.

2. HBM RESIDENCY LEDGER.  Allocation seams register what actually
   lives on device: the DeltaTensorizer's resident cluster (per-table
   bytes per profile), the speculative chain's materialized cluster at
   its pad buckets, prewarm-ladder buffers, and AOT resident executable
   blobs.  ``project()`` scales a registered entry's per-table shapes
   to arbitrary (nodes, pods) — node-axis dims scale linearly, pod-axis
   dims re-bucket through ``pow2_bucket``, kv-vocab dims follow the
   hostname-dominated linear-in-nodes model, everything else is held —
   so "does the 100k x 10k north-star fit per v5e shard" is answerable
   OFFLINE from any ledger snapshot (tools/devplan, /debug/devicez, or
   a bench ``device`` block).  The projection model is validated by the
   capacity-planner sanity gate in tests/test_devstats.py: projected vs
   actually-measured bytes at bench shapes agree within 10%.

3. ROOFLINE JOIN.  Measured device time joins the committed
   ``COMPILE_MANIFEST.json`` cost rows (XLA cost-analysis ``flops`` and
   ``bytes_accessed`` per lowering sha): each program's arithmetic
   intensity classifies it compute- vs memory-bound against the peak
   FLOP/s and peak HBM bandwidth of the device it ran on
   (utils/flops.DEVICE_PEAKS, keyed by ``device_kind``), and achieved
   FLOP/s over the measured seconds yields ``roofline_fraction`` — how
   much of the bound the program actually sustains.  A device with no
   peaks row (the cpu backend included) gets none of those fields.
   Achieved FLOPs come from
   the analytic model where one exists (the gang auction,
   utils/flops.gang_cycle_flops, attributed per fenced cycle) and from
   the manifest cost row scaled by operand bytes otherwise
   (``flops_source`` says which).  Surfaced in ``/debug/devicez``, the
   bench per-case ``device`` block, flight-recorder ``device-fence``
   span args, the pipeline doc's ``device`` block (the traceview
   "device:" digest), and tools/benchtrend.py attribution.

ARMING (the house contract, mirroring utils/slo.py / utils/trace.py):
``KUBETPU_DEVSTATS=1`` or ``arm_devstats()``.  DISARMED (the default)
every seam is ONE module-attribute read and the hot path takes ZERO new
locks — proven by the poison-monkeypatch test — and armed-vs-disarmed
placements are bit-identical (the parity golden): fencing only waits,
it never changes a value.  Importing this module never imports jax.
"""

from __future__ import annotations

import json
import math
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

from .flops import DevicePeaks, device_peaks
from .intern import pow2_bucket

DEVSTATS_ENV = "KUBETPU_DEVSTATS"
SAMPLE_ENV = "KUBETPU_DEVSTATS_SAMPLE"
DEFAULT_SAMPLE_INTERVAL = 8
# the serving programs devstats attributes, mapped to their manifest
# program names (tools/kubecensus traces the jitted inner functions)
PROGRAMS = {
    "run_auction": "_schedule_gang",
    "schedule_sequential": "_schedule_sequential",
    "apply_cluster_delta": "_apply_cluster_delta",
    "explain_verdicts": "_explain_verdicts",
}

_AVAL_RE = re.compile(r"^([a-z_0-9]+)\[([0-9,]*)\]$")
_DTYPE_BYTES = {"bool": 1, "int8": 1, "uint8": 1, "int16": 2, "uint16": 2,
                "bfloat16": 2, "float16": 2, "int32": 4, "uint32": 4,
                "float32": 4, "int64": 8, "uint64": 8, "float64": 8}


def _aval_bytes(aval: str) -> int:
    """Bytes of one manifest aval string ('float32[64,12]')."""
    m = _AVAL_RE.match(aval.strip())
    if not m:
        return 0
    dt, dims = m.groups()
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n * _DTYPE_BYTES.get(dt, 4)


def pytree_nbytes(tree) -> int:
    """Total bytes of a pytree of shaped arrays (jax or numpy) — pure
    shape/dtype arithmetic, no transfer, no sync.  Armed-only helper
    (the import of jax.tree is why)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            n *= int(d)
        total += n * _DTYPE_BYTES.get(str(dtype), 4)
    return total


def table_entries(named_tables: Dict[str, Any]) -> Dict[str, List[dict]]:
    """Per-table leaf entries ({name: [{shape, dtype, bytes}, ...]}) of
    a dict of array pytrees — the ledger registration payload, computed
    OUTSIDE any lock (armed-only; imports jax.tree)."""
    import jax
    out: Dict[str, List[dict]] = {}
    for name, tree in named_tables.items():
        rows = []
        for leaf in jax.tree.leaves(tree):
            shape = getattr(leaf, "shape", None)
            dtype = getattr(leaf, "dtype", None)
            if shape is None or dtype is None:
                continue
            n = 1
            for d in shape:
                n *= int(d)
            rows.append({"shape": [int(d) for d in shape],
                         "dtype": str(dtype),
                         "bytes": n * _DTYPE_BYTES.get(str(dtype), 4)})
        out[name] = rows
    return out


# -------------------------------------------------------- manifest costs


_manifest_cache: Optional[Dict[str, dict]] = None
_manifest_lock = threading.Lock()


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def manifest_costs(path: Optional[str] = None) -> Dict[str, dict]:
    """Per-program cost reference from COMPILE_MANIFEST.json: for each
    manifest program the single-device row with the LARGEST flops (the
    biggest census rung — the most representative arithmetic-intensity
    sample), as {program: {flops, bytes_accessed, in_bytes, variant,
    lowering_sha256}}.  Cached after the first read; an unreadable
    manifest yields an empty map (every roofline degrades to
    timing-only, never an error)."""
    global _manifest_cache
    with _manifest_lock:
        if _manifest_cache is not None and path is None:
            return _manifest_cache
    try:
        with open(path or os.path.join(_repo_root(),
                                       "COMPILE_MANIFEST.json")) as f:
            rows = json.load(f).get("rows", [])
    except (OSError, ValueError):
        rows = []
    out: Dict[str, dict] = {}
    for row in rows:
        if row.get("sharding"):
            continue            # mesh twins: per-shard costs mislead
        prog = row.get("program")
        cost = row.get("cost") or {}
        flops = cost.get("flops")
        ba = cost.get("bytes_accessed")
        if not prog or not isinstance(flops, (int, float)) \
                or not isinstance(ba, (int, float)) or ba <= 0:
            continue
        cur = out.get(prog)
        if cur is None or flops > cur["flops"]:
            out[prog] = {
                "flops": float(flops), "bytes_accessed": float(ba),
                "in_bytes": sum(_aval_bytes(a)
                                for a in row.get("in_avals") or []),
                "variant": row.get("variant"),
                "lowering_sha256": (row.get("lowering_sha256") or "")[:16],
            }
            # per-collective DCN bytes (joined into the census row from
            # EXACT_MANIFEST.json): lets the roofline split arithmetic
            # bandwidth from cross-device transfer per program
            xb = cost.get("collective_bytes")
            if isinstance(xb, dict):
                out[prog]["collective_bytes"] = xb
    if path is None:
        with _manifest_lock:
            _manifest_cache = out
    return out


def roofline(program: str, seconds: float,
             flops: Optional[float] = None,
             in_bytes: Optional[float] = None,
             costs: Optional[Dict[str, dict]] = None,
             peaks: Optional[DevicePeaks] = None) -> Optional[dict]:
    """Join one program's measured device seconds against its manifest
    cost row.  ``flops``: analytically-counted FLOPs executed during
    ``seconds`` (utils/flops) — preferred; without it the manifest row's
    flops are SCALED by operand bytes (``in_bytes`` / row in_bytes, the
    linear model that holds for these memory-shaped programs) and
    ``flops_source`` says "scaled-census".  Returns None when the
    program has no manifest cost row.  ``peaks`` defaults to the running
    device's (utils/flops.device_peaks); the roofline bound is
    min(peak FLOP/s, AI * peak bytes/s), and on a device with no peaks
    row the regime/bound/fraction fields are simply absent."""
    costs = costs if costs is not None else manifest_costs()
    row = costs.get(PROGRAMS.get(program, program))
    if row is None or seconds <= 0:
        return None
    peaks = peaks if peaks is not None else device_peaks()
    ai = row["flops"] / row["bytes_accessed"]
    out = {
        "manifest_variant": row["variant"],
        "lowering_sha256": row["lowering_sha256"],
        "arithmetic_intensity": round(ai, 4),
    }
    bound = None
    if peaks is not None:
        bound = min(peaks.flops_per_s, ai * peaks.hbm_bytes_per_s)
        out["regime"] = ("compute-bound"
                         if ai * peaks.hbm_bytes_per_s >= peaks.flops_per_s
                         else "memory-bound")
        out["roofline_bound_tflops"] = round(bound / 1e12, 3)
    if flops is not None and flops > 0:
        out["flops_source"] = "analytic"
        achieved = flops / seconds
    elif in_bytes and row["in_bytes"] > 0:
        out["flops_source"] = "scaled-census"
        achieved = row["flops"] * (in_bytes / row["in_bytes"]) / seconds
    else:
        return out
    out["achieved_tflops"] = round(achieved / 1e12, 6)
    if bound is not None:
        out["roofline_fraction"] = round(achieved / bound, 6)
    return out


# ------------------------------------------------------------- projection


def project(ledger_doc: Dict[str, Any], nodes: int, pods: int,
            shards: int = 1,
            groups: Optional[Tuple[str, ...]] = None,
            peaks: Optional[DevicePeaks] = None) -> Dict[str, Any]:
    """Capacity projection: scale a ledger snapshot's per-table shapes
    to (nodes, pods) and answer whether the result fits per-chip HBM.

    The per-dim model (validated within 10% at bench shapes by the
    sanity gate in tests/test_devstats.py):

      * a dim equal to the entry's recorded node count scales linearly
        to ``nodes`` (the node axis is exact, never bucketed);
      * a dim equal to the recorded pod-axis bucket re-buckets to
        ``pow2_bucket(pods)``;
      * a dim equal to the recorded kv-vocab cap follows the
        hostname-dominated model ``pow2_bucket(kv0 * nodes/nodes0)`` —
        every node contributes a unique hostname (k, v) pair, so the
        label-pair vocab grows linearly with the node count;
      * every other dim (resource channels, label KEYS, zones, ports,
        taints — content-bounded vocabularies) is held.

    ``shards`` models a mesh that shards the POD axis (parallel/mesh.py
    does): per-shard bytes re-project with pods/shards.  Returns per-
    table and per-group projected bytes plus the fit verdict against
    ``peaks.hbm_bytes`` — the running device's by default
    (utils/flops.device_peaks); no verdict where the device has no
    peaks row."""

    def scale_entry(entry: dict, n_pods: int) -> Tuple[int, Dict[str, int]]:
        axes = entry.get("axes") or {}
        n0 = axes.get("nodes")
        p0 = axes.get("pods")
        kv0 = axes.get("kv")
        p1 = pow2_bucket(max(int(n_pods), 1))
        kv1 = (pow2_bucket(int(math.ceil(kv0 * nodes / n0)))
               if kv0 and n0 else None)
        per_table: Dict[str, int] = {}
        total = 0
        for name, leaves in (entry.get("tables") or {}).items():
            tb = 0
            for leaf in leaves:
                b = leaf.get("bytes", 0)
                shape = leaf.get("shape") or []
                # per-dim role tags stamped at registration
                # (register_cluster) are authoritative — they survive
                # the n0 == p0 collision that value matching cannot
                # (e.g. 2048 nodes with a 2048 pod bucket would
                # otherwise scale the pod axis node-linearly and
                # corrupt the north-star projection).  Entries without
                # tags (opaque byte records, foreign documents) fall
                # back to value matching per dim.
                dims = leaf.get("dims")
                factor = 1.0
                for j, d in enumerate(shape):
                    if dims is not None and j < len(dims):
                        tag = dims[j]
                    elif n0 and d == n0:
                        tag = "nodes"
                    elif p0 and d == p0:
                        tag = "pods"
                    elif kv0 and d == kv0:
                        tag = "kv"
                    else:
                        tag = None
                    if tag == "nodes" and n0:
                        factor *= nodes / n0
                    elif tag == "pods" and p0:
                        factor *= p1 / p0
                    elif tag == "kv" and kv0 and kv1:
                        factor *= kv1 / kv0
                tb += int(math.ceil(b * factor))
            per_table[name] = tb
            total += tb
        return total, per_table

    per_group: Dict[str, int] = {}
    tables: Dict[str, int] = {}
    total = 0
    shard_total = 0
    for key, entry in sorted((ledger_doc.get("entries") or {}).items()):
        if groups is not None and entry.get("group") not in groups:
            continue
        t, per_table = scale_entry(entry, pods)
        st, _ = scale_entry(entry, max(pods // max(shards, 1), 1))
        per_group[key] = t
        total += t
        shard_total += st
        for name, b in per_table.items():
            tables[f"{key}/{name}"] = b
    out = {
        "nodes": int(nodes), "pods": int(pods),
        "pod_bucket": pow2_bucket(max(int(pods), 1)),
        "shards": int(shards),
        "per_group_bytes": per_group,
        "per_table_bytes": tables,
        "total_bytes": total,
        "per_shard_bytes": shard_total,
    }
    peaks = peaks if peaks is not None else device_peaks()
    if peaks is not None:
        out["hbm_bytes_per_chip"] = int(peaks.hbm_bytes)
        out["fits_single_chip"] = total <= peaks.hbm_bytes
        out["fits_per_shard"] = shard_total <= peaks.hbm_bytes
    return out


# ---------------------------------------------------------------- DevStats


class DevStats:
    """Per-program device-time records + the residency ledger.

    Lock-guarded: the serving thread records, /debug/devicez and the
    bench read concurrently.  All derivation (shape walks, byte sums,
    roofline math) happens OUTSIDE the lock — only dict updates run
    under it (concurrency-family contract, like utils/slo.py)."""

    def __init__(self, sample_interval: Optional[int] = None):
        si = sample_interval if sample_interval is not None else int(
            os.environ.get(SAMPLE_ENV, str(DEFAULT_SAMPLE_INTERVAL)))
        self.sample_interval = max(int(si), 1)
        self._lock = threading.Lock()
        self._programs: Dict[str, dict] = {}  # kubelint: guarded-by(_lock)
        self._entries: Dict[str, dict] = {}   # kubelint: guarded-by(_lock)
        self._cycles = 0                      # kubelint: guarded-by(_lock)
        self._deep = False                    # kubelint: guarded-by(_lock)
        self.fenced_cycles = 0                # kubelint: guarded-by(_lock)
        self.fence_wait_s = 0.0               # kubelint: guarded-by(_lock)
        self._xplane: Optional[dict] = None   # kubelint: guarded-by(_lock)

    # ---- sampling --------------------------------------------------------

    def begin_cycle(self) -> bool:
        """Serving-thread cycle tick: every ``sample_interval``-th cycle
        is a deep-timing cycle — its dispatches are micro-fenced.  The
        flag latches until the next tick so the cycle's later seams
        (delta apply, dispatch) agree on the decision.  Phase: the
        FIRST cycle after arming (or a bench-case clear()) is deep, so
        a drain shorter than the interval still yields at least one
        measured sample (compile cost can't pollute it — jit traces and
        compiles synchronously in the dispatch call, before the fence
        timer starts)."""
        with self._lock:
            self._cycles += 1
            self._deep = (self._cycles - 1) % self.sample_interval == 0
            if self._deep:
                self.fenced_cycles += 1
            return self._deep

    def deep_active(self) -> bool:
        with self._lock:
            return self._deep

    # ---- per-program device time ----------------------------------------

    def record_program(self, program: str, seconds: float,
                       source: str = "fence",
                       in_bytes: Optional[int] = None) -> None:
        """Fold one measured device-time sample in.  source: "fence"
        (block_until_ready micro-fence), "sync" (a naturally-blocking
        readback, e.g. explain_verdicts), "xplane" (profiler capture)."""
        s = max(float(seconds), 0.0)
        with self._lock:
            st = self._programs.get(program)
            if st is None:
                st = self._programs[program] = {
                    "count": 0, "sum_s": 0.0, "min_s": math.inf,
                    "max_s": 0.0, "last_s": 0.0, "sources": {},
                    "in_bytes_sum": 0, "flops_sum": 0.0,
                    "flops_time_s": 0.0}
            st["count"] += 1
            st["sum_s"] += s
            st["min_s"] = min(st["min_s"], s)
            st["max_s"] = max(st["max_s"], s)
            st["last_s"] = s
            st["sources"][source] = st["sources"].get(source, 0) + 1
            if in_bytes:
                st["in_bytes_sum"] += int(in_bytes)
            if source == "fence":
                self.fence_wait_s += s

    def attribute_flops(self, program: str, flops: float,
                        seconds: Optional[float] = None) -> None:
        """Pair analytically-counted FLOPs with a recorded sample's
        seconds (the scheduler knows the auction's round count — and so
        its flops — only after the readback, one seam later than the
        fence).  Callers pass the SAMPLE'S OWN fence seconds: under a
        sampling interval smaller than the pipeline depth, newer fence
        samples land before the older cycle's commit runs, so "the last
        sample" would mispair; last_s is only the fallback."""
        with self._lock:
            st = self._programs.get(program)
            if st is None or not st["count"]:
                return
            st["flops_sum"] += float(flops)
            st["flops_time_s"] += (float(seconds) if seconds is not None
                                   else st["last_s"])

    def program_stats(self, program: str) -> Optional[dict]:
        with self._lock:
            st = self._programs.get(program)
            return dict(st) if st is not None else None

    def mean_seconds(self, program: str) -> float:
        """Mean measured device seconds per sampled dispatch of a
        program (0.0 when never sampled) — bench estimates a drain's
        total device time as mean * cycle count."""
        with self._lock:
            st = self._programs.get(program)
            if st is None or not st["count"]:
                return 0.0
            return st["sum_s"] / st["count"]

    # ---- residency ledger ------------------------------------------------

    def record_ledger(self, group: str, profile: str,
                      tables: Dict[str, List[dict]],
                      axes: Optional[Dict[str, int]] = None,
                      meta: Optional[Dict[str, Any]] = None) -> None:
        """(Re-)register one allocation seam's resident tables.  Keyed
        (group, profile): a re-registration REPLACES the previous one —
        the ledger describes what is resident NOW, not history.  tables:
        ``table_entries()`` output, computed by the caller outside this
        lock."""
        total = sum(leaf.get("bytes", 0)
                    for leaves in tables.values() for leaf in leaves)
        entry = {"group": group, "profile": profile,
                 "tables": tables, "axes": dict(axes or {}),
                 "bytes": total, "meta": dict(meta or {})}
        key = f"{group}/{profile}" if profile else group
        with self._lock:
            prev = self._entries.get(key)
            entry["registrations"] = (prev["registrations"] + 1
                                      if prev else 1)
            self._entries[key] = entry

    def record_bytes(self, group: str, profile: str, name: str,
                     nbytes: int) -> None:
        """Register one opaque resident allocation (e.g. a deserialized
        AOT executable blob) by NAME within the (group, profile) entry.
        Re-registering the same name REPLACES the previous bytes —
        a restarted runtime (or a bench attempt's fresh Scheduler)
        re-loading the same artifact describes the SAME residency, and
        an additive ledger would grow without bound while real HBM use
        did not."""
        key = f"{group}/{profile}" if profile else group
        leaf = {"shape": [], "dtype": "bytes", "bytes": int(nbytes)}
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                entry = self._entries[key] = {
                    "group": group, "profile": profile, "tables": {},
                    "axes": {}, "bytes": 0, "meta": {},
                    "registrations": 0}
            prev = entry["tables"].get(name)
            if prev:
                entry["bytes"] -= sum(l.get("bytes", 0) for l in prev)
            entry["tables"][name] = [leaf]
            entry["bytes"] += int(nbytes)
            entry["registrations"] += 1

    def has_group(self, group: str) -> bool:
        with self._lock:
            return any(e["group"] == group
                       for e in self._entries.values())

    def drop_group(self, group: str,
                   profile: Optional[str] = None) -> None:
        """Unregister a group's entries (all profiles, or one) — the
        ledger describes what is resident NOW: a discarded speculative
        chain's cluster is freed device memory and must stop counting
        against the capacity projection."""
        with self._lock:
            for k in [k for k, e in self._entries.items()
                      if e["group"] == group
                      and (profile is None or e["profile"] == profile)]:
                del self._entries[k]

    def ledger(self) -> Dict[str, Any]:
        """The ledger snapshot tools/devplan projects from."""
        with self._lock:
            entries = {k: {**v, "tables": {n: [dict(l) for l in ls]
                                           for n, ls in
                                           v["tables"].items()}}
                       for k, v in self._entries.items()}
        return {"entries": entries,
                "total_bytes": sum(e["bytes"] for e in entries.values())}

    # ---- xplane ----------------------------------------------------------

    def ingest_xplane(self, log_dir: str) -> dict:
        """Best-effort XPlane ingestion from a jax.profiler capture dir
        (trace.capture_device_trace calls this on exit when armed).
        When the profiler analysis tooling is importable, per-program
        device durations fold in as "xplane"-source samples; when it is
        not (the common serving image), the REASON is recorded — the
        capture is never silently dropped."""
        status: Dict[str, Any] = {"dir": log_dir}
        paths: List[str] = []
        for dp, _dirs, fs in os.walk(log_dir):
            paths.extend(os.path.join(dp, f) for f in fs
                         if f.endswith(".xplane.pb"))
        status["captures"] = len(paths)
        records = 0
        if not paths:
            status["available"] = False
            status["reason"] = "no .xplane.pb capture found"
        else:
            try:
                # the TensorBoard profiler plugin's converter is the
                # only public XPlane parser; serving images usually
                # don't ship it
                from tensorflow.python.profiler.internal import _pywrap_profiler  # noqa: F401
                status["available"] = True
            except Exception as e:
                status["available"] = False
                status["reason"] = ("xplane tooling unavailable "
                                    f"({type(e).__name__}); deep-timing "
                                    "fences remain the measured source")
            else:  # pragma: no cover - profiler tooling not in CI image
                for p in paths:
                    for prog, secs in _parse_xplane(p).items():
                        self.record_program(prog, secs, source="xplane")
                        records += 1
        status["records"] = records
        with self._lock:
            self._xplane = status
        return status

    # ---- reads -----------------------------------------------------------

    def clear(self) -> None:
        """Drop program samples and the fence accounting; the ledger
        (what is resident) survives — bench calls this between attempts
        so each case's ``device`` block describes one drain."""
        with self._lock:
            self._programs.clear()
            self.fenced_cycles = 0
            self.fence_wait_s = 0.0
            self._cycles = 0
            self._deep = False

    def to_dict(self) -> Dict[str, Any]:
        """The /debug/devicez document: per-program measured device
        time + roofline join, the residency ledger, and the sampling
        overhead accounting."""
        with self._lock:
            programs = {k: dict(v) for k, v in self._programs.items()}
            cycles = self._cycles
            fenced = self.fenced_cycles
            fence_s = self.fence_wait_s
            xplane = dict(self._xplane) if self._xplane else None
        costs = manifest_costs()
        progs_out: Dict[str, Any] = {}
        for name, st in sorted(programs.items()):
            d = {"count": st["count"],
                 "device_time_s": round(st["sum_s"], 6),
                 "mean_s": round(st["sum_s"] / max(st["count"], 1), 6),
                 "min_s": round(st["min_s"], 6) if st["count"] else 0.0,
                 "max_s": round(st["max_s"], 6),
                 "last_s": round(st["last_s"], 6),
                 "sources": dict(st["sources"])}
            flops = st["flops_sum"] if st["flops_time_s"] > 0 else None
            secs = (st["flops_time_s"] if flops is not None
                    else st["sum_s"])
            mean_in = (st["in_bytes_sum"] / st["count"]
                       if st["in_bytes_sum"] and st["count"] else None)
            rl = roofline(name, secs, flops=flops,
                          in_bytes=(mean_in * st["count"]
                                    if mean_in else None),
                          costs=costs)
            if rl is not None:
                d["roofline"] = rl
            progs_out[name] = d
        doc = {"armed": True,
               "sample_interval": self.sample_interval,
               "cycles_seen": cycles,
               "fenced_cycles": fenced,
               "fence_wait_s": round(fence_s, 6),
               "programs": progs_out,
               "ledger": self.ledger()}
        if xplane is not None:
            doc["xplane"] = xplane
        return doc

    def summary(self) -> Dict[str, Any]:
        """Compact block for the pipeline doc / bench ``device`` JSON:
        per-program {count, device_time_s, mean_s, achieved/fraction}
        plus resident-byte totals per ledger group."""
        doc = self.to_dict()
        progs = {}
        for name, d in doc["programs"].items():
            p = {"count": d["count"],
                 "device_time_s": d["device_time_s"],
                 "mean_s": d["mean_s"]}
            rl = d.get("roofline")
            if rl:
                for k in ("achieved_tflops", "roofline_fraction",
                          "regime", "flops_source"):
                    if k in rl:
                        p[k] = rl[k]
            progs[name] = p
        groups: Dict[str, int] = {}
        for key, e in doc["ledger"]["entries"].items():
            groups[e["group"]] = groups.get(e["group"], 0) + e["bytes"]
        return {"sample_interval": doc["sample_interval"],
                "fenced_cycles": doc["fenced_cycles"],
                "fence_wait_s": doc["fence_wait_s"],
                "programs": progs,
                "ledger_bytes": doc["ledger"]["total_bytes"],
                "ledger_group_bytes": groups}


def _parse_xplane(path: str) -> Dict[str, float]:  # pragma: no cover
    """Placeholder for environments that DO ship the profiler tooling;
    the CI image does not, so ingest_xplane records the reason
    instead."""
    return {}


# ----------------------------------------------------- module arming state
#
# Read WITHOUT a lock on the hot path (rebinding a Python reference is
# atomic; a racing reader sees old or new), exactly like utils/slo.py's
# _tracker.  arm/disarm serialize via _devstats_lock.

_stats: Optional[DevStats] = None
_devstats_lock = threading.Lock()


def devstats() -> Optional[DevStats]:
    """The armed DevStats, or None (disarmed, the default)."""
    return _stats


def arm_devstats(sample_interval: Optional[int] = None) -> DevStats:
    """Idempotently arm device-side observability (returns the existing
    instance if already armed)."""
    global _stats
    with _devstats_lock:
        if _stats is None:
            _stats = DevStats(sample_interval=sample_interval)
        return _stats


def disarm_devstats() -> None:
    global _stats
    with _devstats_lock:
        _stats = None


def maybe_arm_from_env() -> Optional[DevStats]:
    """Scheduler-construction hook: arms iff KUBETPU_DEVSTATS=1."""
    if os.environ.get(DEVSTATS_ENV, "0") not in ("", "0", "false",
                                                 "False"):
        return arm_devstats()
    return None


# --------------------------------------------------- registration helpers

# ClusterTensors tables whose dim 0 is NOT the node axis: the vocab-side
# metadata rows ([T]/[I]) and the flattened term tensors ([E, .]) — a
# coincidental dim-0 == node-count match must not tag them node-scaled
_NODE_AXIS0_EXCLUDE = ("taint_is_hard", "taint_is_prefer", "image_size",
                       "image_spread", "filter_terms", "score_terms")


def _tag_cluster_dims(entries: Dict[str, List[dict]],
                      axes: Dict[str, int]) -> None:
    """Stamp per-dim role tags ("nodes"/"pods"/"kv"/None) onto a
    registered cluster's leaf entries using the ClusterTensors layout:
    dim 0 of a ``pod_*`` table IS the pod axis and dim 0 of any other
    (non-vocab, non-term) table IS the node axis — authoritative even
    when the node count and pod bucket coincide, which pure value
    matching cannot disambiguate (see project())."""
    n, p, kv = axes.get("nodes"), axes.get("pods"), axes.get("kv")
    for name, leaves in entries.items():
        pod_table = name.startswith("pod_")
        node_dim0 = (not pod_table and name not in _NODE_AXIS0_EXCLUDE)
        for leaf in leaves:
            tags: List[Optional[str]] = []
            for i, d in enumerate(leaf["shape"]):
                if i == 0 and pod_table and d == p:
                    tags.append("pods")
                elif i == 0 and node_dim0 and d == n:
                    tags.append("nodes")
                elif i > 0 and d == kv:
                    tags.append("kv")
                elif i > 0 and d == p:
                    tags.append("pods")
                elif i > 0 and d == n:
                    tags.append("nodes")
                else:
                    tags.append(None)
            leaf["dims"] = tags


def register_cluster(group: str, profile: str, cluster,
                     n_nodes: int, meta: Optional[Dict[str, Any]] = None
                     ) -> None:
    """Register a resident ClusterTensors' per-table bytes under
    (group, profile) — the DeltaTensorizer resident, the speculative
    chain, a prewarm-ladder rung.  No-op disarmed (one attribute
    read); the shape walk runs outside the ledger lock."""
    ds = _stats
    if ds is None:
        return
    named = {name: getattr(cluster, name)
             for name in type(cluster)._fields}
    axes = {"nodes": int(n_nodes),
            "pods": int(cluster.pod_valid.shape[0]),
            "kv": int(cluster.kv.shape[1])}
    entries = table_entries(named)
    _tag_cluster_dims(entries, axes)
    ds.record_ledger(group, profile, entries, axes=axes, meta=meta)
