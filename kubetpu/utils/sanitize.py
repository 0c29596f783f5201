"""Runtime sanitizer harness: the dynamic half of the kubelint contract.

kubelint (tools/kubelint) proves hot-path invariants statically; this
module enforces the ones only a live trace can check, behind one opt-in
switch (``KUBETPU_SANITIZE=1``):

  * ``jax_debug_nans`` — a NaN anywhere in filter/score math means a
    broken kernel (every score is finite by construction); fail loudly at
    the producing primitive instead of binding a garbage placement.
  * ``jax_numpy_rank_promotion="raise"`` — every broadcast in the kernels
    is explicit (``[None, :]``); an implicit rank promotion is almost
    always a transposed operand riding a silent broadcast.
  * donation-mismatch logging — a donated buffer XLA could not reuse
    means the donation annotation and the program disagree; surfaced
    every time instead of Python's warn-once default.
  * a per-program compile-count watchdog — with pow2 bucketing
    (utils/intern.py) every jitted program must compile AT MOST ONCE per
    (program, shape-bucket) key per process; a second compile of the same
    key means the jit cache is being defeated (fresh jit objects,
    unhashable statics, dtype drift).  Tests run a scheduling cycle under
    the sanitizer and fail on any recompilation.

The sanitizer deliberately does NOT flip ``jax_enable_x64`` — the scoring
pipeline is calibrated for f32 (see ops/kernels.py) — and restores every
config flag it touched on ``disable_sanitizer()``/context exit, so test
suites can scope it to single cases.
"""

from __future__ import annotations

import collections
import logging
import os
import re
import threading
import warnings
from contextlib import contextmanager
from typing import Deque, Dict, List, Optional, Set, Tuple

ENV_FLAG = "KUBETPU_SANITIZE"

# the logger jax routes compilation progress through; records look like
# "Compiling jit(<name>) with global shapes and types (ShapedArray(
# float32[8,16]), ...). Argument mapping: (...)." — one per jit-cache
# miss, emitted at lowering (so a persistent-cache hit still counts: the
# watchdog counts defeated IN-PROCESS jit caches, not XLA seconds)
_PXLA_LOGGER = "jax._src.interpreters.pxla"
_COMPILE_RE = re.compile(
    r"Compiling (\S+) with global shapes and types (\(.*\))\.\s*"
    r"Argument mapping", re.DOTALL)
_JIT_WRAPPER_RE = re.compile(r"jit\((.*)\)")
_DONATION_RE = re.compile(r"[Dd]onated buffers? .*not usable|"
                          r"buffer donat\w+ .*mismatch")
# one argument of a shape signature: "float32[8,16]", "int32[]"
_ARG_RE = re.compile(r"(\w+)\[([\d,]*)\]")
MAX_COMPILE_RECORDS = 256
MAX_SIGNATURES_PER_PROGRAM = 64

Signature = List[Tuple[str, Tuple[int, ...]]]


def parse_signature(shapes: str) -> Signature:
    """The compile record's shape string as [(dtype, dims)...], one entry
    per argument, in argument order."""
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _ARG_RE.findall(shapes)]


def _diff(old: Signature, new: Signature) -> List[str]:
    if len(old) != len(new):
        return [f"args: {len(old)} -> {len(new)}"]
    changes: List[Tuple[int, str]] = []      # (argument, what changed)
    for i, ((dt0, d0), (dt1, d1)) in enumerate(zip(old, new)):
        if dt0 != dt1:
            changes.append((i, f"dtype: {dt0} -> {dt1}"))
        if len(d0) != len(d1):
            changes.append((i, f"rank: {len(d0)} -> {len(d1)}"))
            continue
        changes.extend((i, f"dim {k}: {a} -> {b}")
                       for k, (a, b) in enumerate(zip(d0, d1)) if a != b)
    # one bucket edge moves a run of arguments alike: say it once
    out: List[str] = []
    runs: List[List] = []                    # [first, last, what]
    for i, what in changes:
        for run in runs:
            if run[2] == what and run[1] == i - 1:
                run[1] = i
                break
        else:
            runs.append([i, i, what])
    for first, last, what in runs:
        out.append(f"arg {first} {what}" if first == last
                   else f"args {first}-{last} {what}")
    return out


def signature_differs(seen: List[Signature], new: Signature) -> List[str]:
    """Where ``new`` differs from the NEAREST signature in ``seen`` (the
    one with the fewest differences): argument positions and dimensions,
    e.g. ``arg 7 dim 0: 2048 -> 4096``, a run of arguments that changed
    alike as ``args 69-75 dim 0: 4096 -> 8192``.  Empty when nothing was seen
    before, or when an identical signature was (a recompile)."""
    if not seen:
        return []
    return min((_diff(old, new) for old in seen), key=len)


class CompileWatchdog(logging.Handler):
    """Counts XLA compilations per (program name, shape signature) and
    donation-mismatch complaints, from jax's own compilation log stream.

    The handler listens at DEBUG on the pxla logger (jax emits the compile
    record at DEBUG unless jax_log_compiles is set), so installing it does
    not add stderr noise — ancestor handlers keep their own levels.

    Known coarseness: the compile record does not include jit STATIC
    argument keys, so two compiles of one program at identical shapes but
    different static configs count as a recompile.  That is deliberate
    for the serving contract (a cycle's ProgramConfig is stable; churning
    statics per cycle IS a compile-cache defeat), but scoped test
    contexts should start from fresh counts — ``sanitized()`` resets the
    watchdog when it joins an already-armed sanitizer."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self._lock = threading.Lock()
        self.counts: Dict[Tuple[str, str], int] = {}
        self.donation_mismatches: List[str] = []
        # per program, the signatures seen (parsed), newest last
        self._signatures: Dict[str, List[Signature]] = {}   # kubelint: guarded-by(_lock)
        # what, how long and why, one dict per compile or cache load:
        # program, kind ("compiled" | "cache-load"), seconds, t
        # (wallclock() at the record), differs (signature_differs).
        # Bounded; appended when the compile ENDS, so ``seconds`` is in
        self.records: Deque[Dict[str, object]] = collections.deque(
            maxlen=MAX_COMPILE_RECORDS)
        self._pending = threading.local()

    # logging.Handler interface ----------------------------------------
    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:
            return
        m = _COMPILE_RE.search(msg)
        if m:
            # module names are "jit(<program>)"; consumers (census
            # matching, the per-program tests) key on the bare program
            wrapped = _JIT_WRAPPER_RE.fullmatch(m.group(1))
            key = (wrapped.group(1) if wrapped else m.group(1), m.group(2))
            sig = parse_signature(key[1])
            with self._lock:
                self.counts[key] = self.counts.get(key, 0) + 1
                seen = self._signatures.setdefault(key[0], [])
                differs = signature_differs(seen, sig)
                if sig not in seen:
                    seen.append(sig)
                    del seen[:-MAX_SIGNATURES_PER_PROGRAM]
            # jax logs this record when lowering is done and the backend
            # compile (or the cache load) is about to start, on the
            # thread that called the program; the duration events that
            # say how long it took, and which of the two it was, arrive
            # on the same thread when it ends (note_duration)
            from .trace import wallclock
            self._flush_pending()
            self._pending.record = {
                "program": key[0], "kind": "compiled", "seconds": 0.0,
                "t": wallclock(), "differs": differs, "shapes": key[1]}
            return
        if _DONATION_RE.search(msg):
            with self._lock:
                self.donation_mismatches.append(msg)
            logging.getLogger("kubetpu.sanitize").warning(
                "donation mismatch: %s", msg)

    def note_duration(self, event: str, duration: float) -> None:
        """jax.monitoring duration events, fed by the process's
        CompileTimer: a cache retrieval marks the pending record a
        ``cache-load``; the backend-compile event (fired on both paths,
        last) closes it with its seconds."""
        rec = getattr(self._pending, "record", None)
        if rec is None:
            return
        if event == _CACHE_RETRIEVAL_EV:
            rec["kind"] = "cache-load"
        elif event == _COMPILE_DURATION_EV:
            rec["seconds"] = float(duration)
            self._flush_pending()

    def _flush_pending(self) -> None:
        """Publish this thread's pending record: onto ``records`` and, as
        the flight event ``xla-compile``, onto the cycle open on this
        thread (a compile landing under tensorize, dispatch, the audit
        or the wave is exactly what the recorder exists to attribute;
        disarmed or outside a cycle that half is a no-op).  A record
        whose end was never seen (no compile timer installed, or the
        compile raised) goes out with seconds 0."""
        rec = getattr(self._pending, "record", None)
        if rec is None:
            return
        self._pending.record = None
        shapes = rec.pop("shapes")
        self.records.append(rec)
        if _watchdogs and _watchdogs[-1] is not self:
            return      # one event a compile, from the newest one armed
        from .trace import note_compile_event
        note_compile_event(rec["program"], shapes, kind=rec["kind"],
                           seconds=round(rec["seconds"], 6), t=rec["t"],
                           differs=list(rec["differs"]))

    # warnings interface (jax emits donation mismatches via warnings.warn,
    # not logging — see enable_sanitizer's showwarning hook) -------------
    def note_warning(self, message: str) -> None:
        if _DONATION_RE.search(message):
            with self._lock:
                self.donation_mismatches.append(message)
            logging.getLogger("kubetpu.sanitize").warning(
                "donation mismatch: %s", message)

    # assertions --------------------------------------------------------
    def compile_count(self) -> int:
        with self._lock:
            return sum(self.counts.values())

    def recompiled(self) -> Dict[Tuple[str, str], int]:
        """(program, shapes) keys that compiled more than once — each one
        is a defeated jit cache."""
        with self._lock:
            return {k: c for k, c in self.counts.items() if c > 1}

    def assert_no_recompilation(self) -> None:
        bad = self.recompiled()
        if bad:
            lines = ["%s compiled %d times for shapes %s" % (name, c, shapes)
                     for (name, shapes), c in sorted(bad.items())]
            raise AssertionError(
                "compile-count watchdog: jit cache defeated —\n  "
                + "\n  ".join(lines))

    def reset(self) -> None:
        with self._lock:
            self.counts.clear()
            self.donation_mismatches.clear()
            self._signatures.clear()
        self.records.clear()


class _SanitizerState:
    def __init__(self):
        self.active = False
        self.watchdog: Optional[CompileWatchdog] = None
        self.prev_config: Dict[str, object] = {}
        self.prev_warn_filters: Optional[list] = None
        self.prev_showwarning = None


_state = _SanitizerState()
_state_lock = threading.Lock()

# refcounted pxla-logger arming, shared by enable_sanitizer and
# install_compile_watchdog: the ORIGINAL level/propagate are saved on the
# first arm and restored only when the last armed handler detaches, so a
# watchdog uninstalled while the full sanitizer is still active (or vice
# versa) can't blind the survivor or restore a stale snapshot.  Callers
# hold _state_lock.
_logger_armed: Set[int] = set()   # id()s of handlers _arm_pxla_logger attached
_logger_prev: Optional[Tuple[int, bool]] = None
# the armed watchdogs, for the compile timer to feed (note_duration)
_watchdogs: List["CompileWatchdog"] = []


def _arm_pxla_logger(handler: logging.Handler) -> None:
    global _logger_prev
    logger = logging.getLogger(_PXLA_LOGGER)
    if not _logger_armed:
        _logger_prev = (logger.level, logger.propagate)
        if logger.level == logging.NOTSET or logger.level > logging.DEBUG:
            # jax emits the compile record at DEBUG; opening the logger up
            # would spray every record at ancestor HANDLERS (propagation
            # skips ancestor logger levels), so keep them local to the
            # watchdog while armed
            logger.setLevel(logging.DEBUG)
            logger.propagate = False
    _logger_armed.add(id(handler))
    logger.addHandler(handler)
    if isinstance(handler, CompileWatchdog):
        _watchdogs.append(handler)
        # its records take their kind and seconds from the timer's events
        install_compile_timer()


def _disarm_pxla_logger(handler: logging.Handler) -> None:
    global _logger_prev
    logger = logging.getLogger(_PXLA_LOGGER)
    logger.removeHandler(handler)
    if handler in _watchdogs:
        _watchdogs.remove(handler)
    # only handlers WE armed count toward the restore — an uninstall of a
    # shared watchdog handed out while the sanitizer was active (never
    # armed here) must not release someone else's arming
    _logger_armed.discard(id(handler))
    if not _logger_armed and _logger_prev is not None:
        logger.setLevel(_logger_prev[0])
        logger.propagate = _logger_prev[1]
        _logger_prev = None


def sanitize_enabled() -> bool:
    return os.environ.get(ENV_FLAG, "0") not in ("", "0", "false", "False")


def current_watchdog() -> Optional[CompileWatchdog]:
    return _state.watchdog if _state.active else None


_SANITIZE_FLAGS = (("jax_debug_nans", True),
                   ("jax_numpy_rank_promotion", "raise"))


def enable_sanitizer() -> CompileWatchdog:
    """Idempotently turn the sanitizer on; returns the watchdog."""
    import jax
    with _state_lock:
        if _state.active:
            return _state.watchdog
        for name, value in _SANITIZE_FLAGS:
            _state.prev_config[name] = getattr(jax.config, name)
            jax.config.update(name, value)
        wd = CompileWatchdog()
        # jax reports donation mismatches via warnings.warn (not logging):
        # hook showwarning so the watchdog sees every one, and make them
        # repeat-warn instead of Python's warn-once.  Both the filter list
        # and the hook are restored on disable.
        _state.prev_warn_filters = list(warnings.filters)
        warnings.filterwarnings(
            "always", message=r".*[Dd]onated buffers?.*")
        _state.prev_showwarning = warnings.showwarning

        def showwarning(message, category, filename, lineno, file=None,
                        line=None, _prev=warnings.showwarning):
            wd.note_warning(str(message))
            return _prev(message, category, filename, lineno, file, line)

        warnings.showwarning = showwarning
        _arm_pxla_logger(wd)
        _state.watchdog = wd
        _state.active = True
        logging.getLogger("kubetpu.sanitize").info(
            "sanitizer on: debug_nans, rank_promotion=raise, donation "
            "logging, compile-count watchdog")
        return wd


def disable_sanitizer() -> None:
    """Restore every flag/handler enable_sanitizer() touched."""
    import jax
    with _state_lock:
        if not _state.active:
            return
        for name, value in _state.prev_config.items():
            jax.config.update(name, value)
        _state.prev_config.clear()
        if _state.watchdog is not None:
            _disarm_pxla_logger(_state.watchdog)
        if _state.prev_warn_filters is not None:
            warnings.filters[:] = _state.prev_warn_filters
        if _state.prev_showwarning is not None:
            warnings.showwarning = _state.prev_showwarning
        _state.prev_warn_filters = None
        _state.prev_showwarning = None
        _state.watchdog = None
        _state.active = False


@contextmanager
def sanitized():
    """Scoped sanitizer for tests: restores config on exit.  If the
    sanitizer was already active (e.g. armed process-wide via
    KUBETPU_SANITIZE=1 at import), the context joins it and leaves it
    running on exit instead of tearing it down.

    ::

        with sanitized() as watchdog:
            run_cycle()
            watchdog.assert_no_recompilation()
    """
    owned = not _state.active
    wd = enable_sanitizer()
    if not owned:
        # joining a process-wide sanitizer: scope the counts so this
        # block's assert_no_recompilation() judges only its own work
        wd.reset()
    try:
        yield wd
    finally:
        if owned:
            disable_sanitizer()


def install_compile_watchdog() -> CompileWatchdog:
    """Attach ONLY the compile-count watchdog (no debug_nans, no
    rank-promotion, no warnings hook): the observer the benchmark
    (perfbench/lib/drive.py) and chip_smoke.py need — compile events
    must be recorded without perturbing the measured numerics.  If the full
    sanitizer is already armed, its watchdog is shared.  Pair with
    uninstall_compile_watchdog()."""
    with _state_lock:
        if _state.active:
            return _state.watchdog
        wd = CompileWatchdog()
        _arm_pxla_logger(wd)
        return wd


def uninstall_compile_watchdog(wd: CompileWatchdog) -> None:
    """Detach a watchdog installed by install_compile_watchdog().  A
    watchdog owned by the full sanitizer is left in place (its lifecycle
    belongs to disable_sanitizer)."""
    with _state_lock:
        if _state.active and wd is _state.watchdog:
            return
        _disarm_pxla_logger(wd)


# --------------------------------------------------------- compile timer
#
# The pxla-log watchdog above COUNTS compiles; it cannot time them, and
# with the persistent cache enabled "a compile happened" conflates two
# very different costs: a true XLA backend compile (seconds to minutes)
# and a disk load of a previously compiled executable (milliseconds).
# jax's own monitoring stream separates them:
#
#   /jax/core/compile/backend_compile_duration   fires on BOTH paths (on
#       a cache hit its duration is the deserialization/load time)
#   /jax/compilation_cache/cache_retrieval_time_sec   fires on hits only
#   /jax/compilation_cache/cache_hits | cache_misses  the counts
#
# so true compile seconds = backend total - retrieval total: the timer
# reports compile_s and cache_load_s separately and exactly.

_COMPILE_DURATION_EV = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EV = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_HIT_EV = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EV = "/jax/compilation_cache/cache_miss"


class CompileTimer:
    """Cumulative compile/cache-load seconds from jax.monitoring events.
    Thread-safe; read with snapshot() and diff two snapshots with delta()
    to attribute cost to a measured phase (a warm-up pass, a prewarm)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.backend_s = 0.0          # kubelint: guarded-by(_lock)
        self.cache_load_s = 0.0       # kubelint: guarded-by(_lock)
        self.cache_hits = 0           # kubelint: guarded-by(_lock)
        self.cache_misses = 0         # kubelint: guarded-by(_lock)

    def on_duration(self, event: str, duration: float, **kw) -> None:
        with self._lock:
            if event == _COMPILE_DURATION_EV:
                self.backend_s += duration
            elif event == _CACHE_RETRIEVAL_EV:
                self.cache_load_s += duration
            else:
                return
        # the watchdog's record of this compile learns its kind and its
        # seconds from the same two events
        for wd in list(_watchdogs):
            wd.note_duration(event, duration)

    def on_event(self, event: str, **kw) -> None:
        with self._lock:
            if event == _CACHE_HIT_EV:
                self.cache_hits += 1
            elif event.startswith(_CACHE_MISS_EV):   # cache_miss(es)
                self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return {
                "compile_s": max(self.backend_s - self.cache_load_s, 0.0),
                "cache_load_s": self.cache_load_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
            }

    @staticmethod
    def delta(before: Dict[str, float],
              after: Dict[str, float]) -> Dict[str, float]:
        """after - before, per key (seconds rounded to ms)."""
        out = {}
        for k, v in after.items():
            d = v - before.get(k, 0)
            out[k] = round(d, 3) if isinstance(d, float) else d
        return out


_timer: Optional[CompileTimer] = None
_timer_lock = threading.Lock()


def install_compile_timer() -> CompileTimer:
    """Idempotently install the module's CompileTimer.  jax.monitoring
    offers no per-listener detach, so ONE timer is registered for the
    process lifetime and shared by every caller (cumulative totals;
    consumers diff snapshots)."""
    global _timer
    with _timer_lock:
        if _timer is None:
            import jax.monitoring as _mon
            t = CompileTimer()
            _mon.register_event_duration_secs_listener(t.on_duration)
            _mon.register_event_listener(t.on_event)
            _timer = t
        return _timer


def maybe_enable_from_env() -> Optional[CompileWatchdog]:
    """Serving-path hook: enables the sanitizer iff KUBETPU_SANITIZE=1.
    Called from kubetpu/__init__.py so every entry point (scheduler,
    server, harness) gets it without its own wiring.  Importing
    this module never imports jax; enabling does."""
    if sanitize_enabled():
        return enable_sanitizer()
    return None
