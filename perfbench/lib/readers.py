"""What the per-layer metric readers share.  A reader
(``perfbench/metrics/<name>.py``) is one ``read(ctx)`` that returns a
number, or None when it finds nothing to read; ``ctx`` is what
``drive.run_cell`` collected in a traced run:

  ctx.client           the Client with its stamps
  ctx.cycles           flight-recorder cycle records that started inside
                       the window (``CycleRecord.to_dict()``)
  ctx.trace            ``xplane.summarize`` of the traced sub-window
  ctx.window_compiles  programs compiled or loaded inside the window
  ctx.device, ctx.cell, ctx.seconds, ctx.t0, ctx.n_nodes,
  ctx.resident_pods
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional

from . import stats, xplane
from ..kernels import auction, peaks

AUCTION_PROGRAM = "schedule_gang"
TENSORIZE_STEP = "Tensorizing snapshot and pod batch done"


def _spans(cycle: Dict[str, Any], name: str) -> List[Dict[str, Any]]:
    return [s for s in cycle["spans"] if s["name"] == name]


def stage_ms_per_cycle(ctx, name: str) -> Optional[float]:
    """Mean milliseconds per cycle spent in the flight-recorder spans of
    that name."""
    per = [sum(s["t1"] - s["t0"] for s in _spans(c, name))
           for c in ctx.cycles]
    per = [x for x in per if x > 0]
    return 1e3 * statistics.fmean(per) if per else None


def prepare_ms_per_cycle(ctx) -> Optional[float]:
    """Cycle start to the end of the Trace step "Tensorizing snapshot and
    pod batch done"."""
    per = []
    for c in ctx.cycles:
        ends = [s["t1"] for s in _spans(c, TENSORIZE_STEP)]
        if ends:
            per.append(max(ends) - c["t0"])
    return 1e3 * statistics.fmean(per) if per else None


def readback_wait_ms_per_cycle(ctx) -> Optional[float]:
    per = [sum(float(s["args"].get("device_wait_s", 0.0))
               for s in _spans(c, "packed-readback")) for c in ctx.cycles]
    return 1e3 * statistics.fmean(per) if per else None


def replace_late_p95_ms(ctx) -> Optional[float]:
    """Closed loop: replacement offered - bind seen, binds of the window."""
    t0, t1 = ctx.t0, ctx.t0 + ctx.seconds
    late = [d for t, d in ctx.client.replace_late if t0 <= t < t1]
    return 1e3 * stats.percentile(late, 95) if late else None


def auction_device_ms_per_cycle(ctx) -> Optional[float]:
    n, s = xplane.module_seconds(ctx.trace, AUCTION_PROGRAM)
    return 1e3 * s / n if n else None


def auction_roofline_pct(ctx) -> Optional[float]:
    """Least time for the rounds actually run / traced kernel time."""
    n, s = xplane.module_seconds(ctx.trace, AUCTION_PROGRAM)
    rounds = [c["meta"].get("auction_rounds") for c in ctx.cycles]
    rounds = [r for r in rounds if r]
    if not n or not rounds or s <= 0:
        return None
    pods = [c["meta"].get("pods", 0) for c in ctx.cycles
            if c["meta"].get("auction_rounds")]
    pk = peaks.peak(ctx.device["kind"])
    templates = ctx.cell.config["templates"]
    terms = any(t.get("features") for t in templates.values())
    least = auction.least_seconds(
        batch=int(round(statistics.fmean(pods))), nodes=ctx.n_nodes,
        rounds=statistics.fmean(rounds), flops_per_s=pk.flops_per_s,
        bytes_per_s=pk.bytes_per_s, resident_pods=ctx.resident_pods,
        terms=terms)
    return 100.0 * least["seconds"] / (s / n)
