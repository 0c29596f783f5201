"""Pallas megakernel backend selection (the impure half).

ops/pallas_kernels.py is a kernel module and must stay pure (kubelint
purity family); everything environment- or state-touching about the
backend choice lives here instead:

  * interpret switch: should kernels run under ``interpret=True`` (any
    non-TPU backend, or the KUBETPU_PALLAS_INTERPRET override — read
    ONCE at import so the decision is process-stable and cannot
    silently flip between traces)?
  * support surface: ``unsupported_reason`` is the single authority on
    when ``kernel_backend="pallas"`` may engage; the gang dispatcher
    falls back to the lax path (and records why) on any non-None reason.
  * fallback accounting: a lock-guarded counter by reason, surfaced in
    flight-recorder cycle meta and asserted by tests so a configuration
    that silently always falls back cannot masquerade as a Pallas win.
"""

from __future__ import annotations

import os
import threading
from typing import Dict, Optional

import jax
import numpy as np

# read ONCE at import: "1" forces interpret mode even on TPU (debugging),
# "0" forces compiled mode even off-TPU (will fail without a TPU backend —
# intended for lowering tests only), unset = probe the backend.
_INTERPRET_ENV = os.environ.get("KUBETPU_PALLAS_INTERPRET")

_lock = threading.Lock()
_fallbacks: Dict[str, int] = {}   # kubelint: guarded-by(_lock)
# runtime demotion (the self-healing ladder's pallas->lax rung): set by
# the scheduler's deadline-guarded dispatch when a pallas-backed cycle
# errors or blows its deadline; unsupported_reason() then refuses the
# backend process-wide until reset, so every later cycle — including
# other profiles' — serves the lax oracle path instead of re-tripping
# the same fault
_demotion: Optional[str] = None   # kubelint: guarded-by(_lock)


def interpret_mode() -> bool:
    """True when pallas_call must run under interpret=True: every non-TPU
    backend (the Mosaic compiler is TPU-only), unless explicitly
    overridden.  Trace-time static: the returned value is baked into the
    lowered program, which is correct — an interpret-mode lowering and a
    Mosaic lowering are different programs with different AOT keys."""
    if _INTERPRET_ENV is not None:
        return _INTERPRET_ENV != "0"
    return jax.default_backend() != "tpu"


def unsupported_reason(cfg, intra_batch_topology: bool,
                       batch=None) -> Optional[str]:
    """None when the Pallas backend can serve this (cfg, routing, batch)
    with bit-identical placements; otherwise a short reason string.

    The intra-batch-topology condition mirrors the scheduler's needs_topo
    gate: a term-free batch (no pod (anti-)affinity, no spread
    constraints, no controller spread selectors) is exactly the batch
    whose per-round score surface the megakernel reproduces.

    The batch check closes the one content-dependent hole: the kernel
    scores PodTopologySpread via the no-soft-constraints constant path,
    so a batch whose pods carry whenUnsatisfiable=ScheduleAnyway spread
    constraints must fall back even under intra_batch_topology=False
    (where the lax path evaluates the REAL soft constraints statically).
    Serving batches are host-side numpy at dispatch time, so the
    inspection is free — no device sync.  A caller passing device-array
    batches (never the serving path) skips the check and carries the
    term-free contract itself."""
    demoted = demotion()
    if demoted is not None:
        return "demoted:%s" % demoted
    if intra_batch_topology:
        return "intra-batch-topology"
    from ..ops import pallas_kernels
    for name, _ in cfg.scores:
        if name not in pallas_kernels.SUPPORTED_SCORES:
            return "score:%s" % name
    if batch is not None:
        sv = getattr(getattr(batch, "spread_soft", None), "valid", None)
        if isinstance(sv, np.ndarray) and bool(sv.any()):
            return "soft-spread-constraints"
    return None


def demote(reason: str) -> None:
    """Demote the pallas backend process-wide with a recorded reason
    (scheduler dispatch-recovery hook); idempotent, first reason wins."""
    global _demotion
    with _lock:
        if _demotion is None:
            _demotion = reason


def demotion() -> Optional[str]:
    with _lock:
        return _demotion


def reset_demotion() -> None:
    global _demotion
    with _lock:
        _demotion = None


def note_fallback(reason: str) -> None:
    with _lock:
        _fallbacks[reason] = _fallbacks.get(reason, 0) + 1


def fallback_counts() -> Dict[str, int]:
    with _lock:
        return dict(_fallbacks)


def reset_fallbacks() -> None:
    with _lock:
        _fallbacks.clear()


def effective_backend(cfg, intra_batch_topology: bool,
                      requested: Optional[str], batch=None) -> str:
    """The backend schedule_gang will actually trace for this call."""
    if requested != "pallas":
        return "lax"
    return ("pallas"
            if unsupported_reason(cfg, intra_batch_topology, batch) is None
            else "lax")
