"""Durable cycle journal: every committed scheduling cycle, on disk.

The flight recorder (utils/trace.py) and the decision log are in-memory
rings that die with the process — a production incident or an
interesting placement decision cannot be re-examined after the fact, let
alone re-EXECUTED.  This module is the persistence substrate under both:
when armed (``KUBETPU_JOURNAL=<dir>``, mirroring the KUBETPU_FLIGHT
arming discipline), every committed cycle appends ONE
self-contained record to a bounded, size-capped on-disk journal —

  INPUTS   the cycle's exact device-program inputs: the applied
           ``ClusterDelta`` (or the blessed-resync host-mirror snapshot,
           or the chain-materialize pad buckets), the pod batch with its
           interned vocab slice, the RNG fold counter, the
           ``ProgramConfig`` + profile/config digest, the recorded
           host-plugin mask (``host_ok``) and host score bias,
           ``pipeline_depth`` and ``ring_slot``
  OUTPUTS  the packed placement vector (chosen / n_feasible /
           unresolvable / rounds), per-pod placements by name, and a
           per-plugin verdict summary folded from the decision audit
  LINKAGE  the flight-recorder cycle seq (``/debug/flightz``) and the
           decision-audit cycle (``/debug/explain``) so a journal record
           cross-references the in-memory observability for as long as
           those rings still hold it

— and ``tools/kubereplay`` re-executes any journaled window offline,
bit-matching replayed placements against the recorded ones (the same
oracle discipline as the AOT gate: a divergence is a
correctness failure, attributed to the first divergent cycle), or
re-runs the window under a modified profile (``--counterfactual``) to
turn every recorded trace into an eval set — the gating substrate for
ROADMAP item 3's learned-scorer work.

On-disk format: one file per record (``cyc-<seq>.rec``) under the armed
directory — a magic/version header, a crc32 of the payload, the payload
length, then the pickled record dict.  Self-contained files make
size-cap eviction an unlink (oldest first, every eviction counted in
``scheduler_journal_dropped_total`` — never silent) and isolate
corruption: a record truncated by a crash (or the ``journal`` chaos
point) fails its crc and is SKIPPED with a per-record reason at read
time instead of poisoning the window.

Bounded-disk contract: at most ``KUBETPU_JOURNAL_MAX_BYTES`` (default
256 MiB) of records are retained.  A replay window must start at a
resync record (the full-snapshot anchor); evicting one orphans the
delta/chain records behind it, which kubereplay skips with reason
``broken-lineage`` until the next anchor.

Arming contract (the poison test in tests/test_journal.py enforces it
exactly like trace's): DISARMED (the default) every seam is
one module-attribute read — the serving hot path takes ZERO new locks
and allocates no journal state; armed-vs-disarmed placements are
bit-identical (the journal only observes).  Importing this module never
imports jax.

Write-failure contract: an armed append that fails for ANY reason (disk
full, chaos ``journal:error``, an unpicklable capture) degrades to a
counted drop (``dropped_total`` + the metric) — recording must never
fail a scheduling cycle.
"""

from __future__ import annotations

import binascii
import os
import pickle
import struct
import threading
from typing import Any, Dict, Iterator, List, Optional, Tuple

JOURNAL_ENV = "KUBETPU_JOURNAL"
MAX_BYTES_ENV = "KUBETPU_JOURNAL_MAX_BYTES"
DEFAULT_MAX_BYTES = 256 << 20

# record file framing: magic + u32 crc32(payload) + u64 len(payload)
MAGIC = b"KTPJ1"
_HEADER = struct.Struct(">5sIQ")
RECORD_VERSION = 1

# journal record input kinds (the state/delta capture seam's vocabulary):
#   resync  payload = pickled HostClusterArrays (the blessed full-snapshot
#           anchor: initial build, anti-entropy, vocab growth, pod-axis
#           growth, verify-divergence)
#   delta   payload = pickled (ClusterDelta, terms-or-None) applied to the
#           previous record's cluster by programs.apply_cluster_delta;
#           terms = (the term tables that crossed whole, by field; one
#           TermsDelta a table: the rows written), applied first by
#           programs.apply_terms_delta
#   chain   payload = (pad_pods, pad_terms): the cluster is the PREVIOUS
#           record's auction materialized at these pow2 pad buckets
#           (models/gang.materialize_assigned, extend_score_terms=True)
#   noop    zero-dirty delta cycle: the previous record's cluster, as is
INPUT_KINDS = ("resync", "delta", "chain", "noop")


class JournalCorrupt(ValueError):
    """A record file whose framing, crc or pickle does not check out —
    the reader-side skip reason, never an abort."""


def _env_max_bytes() -> int:
    """KUBETPU_JOURNAL_MAX_BYTES, tolerant of junk: a malformed value
    (e.g. "256MiB") falls back to the default with a warning instead of
    crashing Scheduler construction through arm_journal."""
    raw = os.environ.get(MAX_BYTES_ENV, "")
    if not raw:
        return DEFAULT_MAX_BYTES
    try:
        return int(raw)
    except ValueError:
        import logging
        logging.getLogger("kubetpu").warning(
            "%s=%r is not an integer byte count; using the default %d",
            MAX_BYTES_ENV, raw, DEFAULT_MAX_BYTES)
        return DEFAULT_MAX_BYTES


def record_filename(seq: int) -> str:
    return "cyc-%012d.rec" % seq


def encode_record(record: Dict[str, Any]) -> bytes:
    payload = pickle.dumps(record, protocol=4)
    return _HEADER.pack(MAGIC, binascii.crc32(payload) & 0xFFFFFFFF,
                        len(payload)) + payload


def decode_record(blob: bytes) -> Dict[str, Any]:
    """Inverse of encode_record; raises JournalCorrupt on any framing,
    length, crc or unpickling failure."""
    if len(blob) < _HEADER.size:
        raise JournalCorrupt("truncated header "
                             f"({len(blob)} < {_HEADER.size} bytes)")
    magic, crc, n = _HEADER.unpack_from(blob)
    if magic != MAGIC:
        raise JournalCorrupt(f"bad magic {magic!r}")
    payload = blob[_HEADER.size:]
    if len(payload) != n:
        raise JournalCorrupt(f"truncated payload ({len(payload)} of {n} "
                             "bytes)")
    if binascii.crc32(payload) & 0xFFFFFFFF != crc:
        raise JournalCorrupt("crc mismatch")
    try:
        rec = pickle.loads(payload)
    except Exception as e:
        raise JournalCorrupt(f"unpicklable payload: {e!r}")
    if not isinstance(rec, dict) or "seq" not in rec:
        raise JournalCorrupt("payload is not a journal record dict")
    return rec


class CycleJournal:
    """The armed journal: a directory of self-contained record files plus
    the counters the ``scheduler_journal_*`` metrics sync from.

    Threading: ``next_seq``/``append`` run on the serving thread; the
    status/linkage reads run on the HTTP debug thread — the counter and
    file-index state is lock-guarded.  File WRITES happen outside the
    lock (one writer, the serving thread, so index order still matches
    file order; blocking I/O must never stall a concurrent status
    read)."""

    def __init__(self, directory: str, max_bytes: Optional[int] = None):
        self.dir = directory
        self.max_bytes = (max_bytes if max_bytes is not None
                          else _env_max_bytes())
        os.makedirs(self.dir, exist_ok=True)
        self._lock = threading.Lock()
        # seq -> on-disk size, insertion-ordered (dicts preserve order);
        # rebuilt from the directory at arm time so a restarted process
        # appends after the previous run's records
        self._files: Dict[int, int] = {}       # kubelint: guarded-by(_lock)
        self._seq = 0                          # kubelint: guarded-by(_lock)
        # running on-disk total (maintained on insert/evict so neither
        # the per-append cap check nor a /debug/journal scrape walks the
        # whole file index under the lock)
        self._disk_total = 0                   # kubelint: guarded-by(_lock)
        self.records_total = 0                 # kubelint: guarded-by(_lock)
        self.bytes_written = 0                 # kubelint: guarded-by(_lock)
        self.dropped_total = 0                 # kubelint: guarded-by(_lock)
        # (journal seq, flight seq, decision cycle, sched cycle) of recent
        # appends — the traceview linkage digest's feed, bounded
        self._links: List[Tuple[int, int, int, int]] = []  # kubelint: guarded-by(_lock)
        self._max_links = 512
        for name in sorted(os.listdir(self.dir)):
            if not (name.startswith("cyc-") and name.endswith(".rec")):
                continue
            try:
                seq = int(name[4:-4])
                size = os.path.getsize(os.path.join(self.dir, name))
            except (ValueError, OSError):
                continue
            self._files[seq] = size
            self._disk_total += size
            self._seq = max(self._seq, seq)

    # -- write side (serving thread) ---------------------------------------

    def next_seq(self) -> int:
        """Reserve the next record id.  Called at commit start: the id
        the cycle's record will be appended under."""
        with self._lock:
            self._seq += 1
            return self._seq

    def note_drop(self, n: int = 1) -> None:
        """Count a record that could not be recorded (build or write
        failure) — the degrade-to-drop half of the write contract."""
        with self._lock:
            self.dropped_total += n

    def append(self, record: Dict[str, Any]) -> bool:
        """Write one record file; True when it landed.  Any failure —
        including an injected ``journal`` chaos fault — degrades to a
        counted drop.  Size-cap eviction (oldest records unlinked) runs
        after a successful write and counts as drops too."""
        from . import chaos
        seq = int(record["seq"])
        path = os.path.join(self.dir, record_filename(seq))
        try:
            blob = encode_record(record)
            act = chaos.action("journal")
            if act == "error":
                raise OSError("injected journal write fault")
            if act == "truncate":
                # a crash mid-write: half the frame reaches the disk
                blob = blob[:max(len(blob) // 2, 1)]
            elif act == "corrupt":
                # a flipped byte INSIDE the payload: framing intact, crc
                # check catches it at read time
                mid = _HEADER.size + max((len(blob) - _HEADER.size) // 2, 0)
                mid = min(mid, len(blob) - 1)
                blob = blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]
            with open(path, "wb") as f:
                f.write(blob)
        except Exception:
            try:
                os.unlink(path)
            except OSError:
                pass
            self.note_drop()
            return False
        evict: List[int] = []
        with self._lock:
            self._files[seq] = len(blob)
            self.records_total += 1
            self.bytes_written += len(blob)
            self._links.append((seq, int(record.get("links", {})
                                         .get("flight_seq", 0) or 0),
                                int(record.get("links", {})
                                    .get("decision_cycle", 0) or 0),
                                int(record.get("cycle", 0) or 0)))
            del self._links[:-self._max_links]
            self._disk_total += len(blob)
            while self._disk_total > self.max_bytes \
                    and len(self._files) > 1:
                old = next(iter(self._files))
                self._disk_total -= self._files.pop(old)
                self.dropped_total += 1
                evict.append(old)
        for old in evict:
            try:
                os.unlink(os.path.join(self.dir, record_filename(old)))
            except OSError:
                pass
        return True

    # -- read side ---------------------------------------------------------

    def counters(self) -> Tuple[int, int]:
        """(records_total, dropped_total) — the scheduler_journal_*
        metric sync's feed (monotonic)."""
        with self._lock:
            return self.records_total, self.dropped_total

    def seqs(self) -> List[int]:
        with self._lock:
            return sorted(self._files)

    def disk_bytes(self) -> int:
        with self._lock:
            return self._disk_total

    def status(self, flight_seqs: Optional[set] = None,
               decision_cycles: Optional[set] = None) -> Dict[str, Any]:
        """The /debug/journal + traceview digest document.  When the
        caller passes the flight recorder's live ring seqs (and/or the
        decision log's live cycle set), linkage hit-rates report what
        fraction of recent journal records still cross-reference a live
        in-memory entry."""
        with self._lock:
            seqs = sorted(self._files)
            links = list(self._links)
            doc: Dict[str, Any] = {
                "armed": True,
                "dir": self.dir,
                "max_bytes": self.max_bytes,
                "records": len(seqs),
                "bytes": self._disk_total,
                "records_total": self.records_total,
                "dropped_total": self.dropped_total,
            }
        if seqs:
            doc["first_seq"] = seqs[0]
            doc["last_seq"] = seqs[-1]
        cycles = [c for (_s, _f, _d, c) in links if c]
        if cycles:
            doc["cycle_span"] = [min(cycles), max(cycles)]
        flagged = [(s, f, d) for (s, f, d, _c) in links]
        with_flight = sum(1 for (_s, f, _d) in flagged if f > 0)
        doc["flight_linked"] = with_flight
        if flagged:
            doc["flight_link_rate"] = round(with_flight / len(flagged), 3)
            if flight_seqs is not None:
                live = sum(1 for (_s, f, _d) in flagged
                           if f in flight_seqs)
                doc["flight_live_rate"] = round(live / len(flagged), 3)
            if decision_cycles is not None:
                live = sum(1 for (_s, _f, d) in flagged
                           if d in decision_cycles)
                doc["decision_live_rate"] = round(live / len(flagged), 3)
        return doc


def read_records(directory: str) -> Iterator[Tuple[int, Optional[Dict],
                                                   Optional[str]]]:
    """Yield ``(seq, record, skip_reason)`` for every record file in seq
    order — exactly one of record/skip_reason is None.  Corrupt or
    truncated files (crash, chaos ``journal`` point) yield a per-record
    reason instead of aborting the window; kubereplay surfaces them in
    its report."""
    try:
        names = sorted(n for n in os.listdir(directory)
                       if n.startswith("cyc-") and n.endswith(".rec"))
    except OSError as e:
        raise FileNotFoundError(f"journal directory unreadable: {e}")
    for name in names:
        try:
            seq = int(name[4:-4])
        except ValueError:
            continue
        path = os.path.join(directory, name)
        try:
            with open(path, "rb") as f:
                blob = f.read()
            rec = decode_record(blob)
        except JournalCorrupt as e:
            yield seq, None, str(e)
            continue
        except OSError as e:
            yield seq, None, f"unreadable: {e}"
            continue
        if int(rec.get("seq", -1)) != seq:
            yield seq, None, (f"seq mismatch (file {seq}, "
                              f"payload {rec.get('seq')})")
            continue
        yield seq, rec, None


def config_digest(mode: str, profile: str, cfg,
                  hard_weight: float) -> str:
    """Stable digest of the profile/program configuration a record was
    produced under.  kubereplay surfaces the distinct digests of a
    window (``config_digests`` in its report): a window spanning more
    than one mixes program configurations (a rollout landed mid-window)
    and should be partitioned before being used as an eval set."""
    import hashlib
    text = repr((RECORD_VERSION, mode, profile, tuple(cfg.filters),
                 tuple(cfg.scores), cfg.hostname_topokey,
                 tuple(cfg.plugin_args), cfg.percentage_of_nodes_to_score,
                 tuple(cfg.active_topo_keys), float(hard_weight)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# ---------------------------------------------------------------- arming
#
# Same contract as trace.py's recorder and chaos.py's registry: _journal is read WITHOUT a lock on the hot path (rebinding a
# reference is atomic; a racing reader sees old or new), arm/disarm
# serialize through _journal_lock.

_journal: Optional[CycleJournal] = None
_journal_lock = threading.Lock()


def journal() -> Optional[CycleJournal]:
    """The armed journal, or None (disarmed, the default)."""
    return _journal


def arm_journal(directory: str,
                max_bytes: Optional[int] = None) -> CycleJournal:
    """Idempotently arm the journal (an already-armed journal for ANY
    directory wins — one journal per process)."""
    global _journal
    with _journal_lock:
        if _journal is None:
            _journal = CycleJournal(directory, max_bytes=max_bytes)
        return _journal


def disarm_journal() -> None:
    global _journal
    with _journal_lock:
        _journal = None


def maybe_arm_from_env() -> Optional[CycleJournal]:
    """Scheduler-construction hook: arms iff KUBETPU_JOURNAL names a
    directory.  An unwritable directory disarms with a warning rather
    than failing scheduler construction."""
    directory = os.environ.get(JOURNAL_ENV, "")
    if not directory:
        return None
    if _journal is not None:
        return _journal
    try:
        return arm_journal(directory)
    except OSError:
        import logging
        logging.getLogger("kubetpu").warning(
            "KUBETPU_JOURNAL=%r is not a writable directory; journal "
            "disarmed", directory)
        return None
