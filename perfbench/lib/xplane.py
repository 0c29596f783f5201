"""Reduction of a jax.profiler trace (``*.xplane.pb``) to the numbers the
benchmark reports: device busy seconds, per-program and per-operation
device time, and the idle gaps named by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  Layout relied on (checked
by tests/perfbench against the trace committed in perfbench/testdata):

  device planes   name starts with "/device:TPU:"; the line "XLA Ops" holds
                  one event per executed HLO operation, the line
                  "XLA Modules" one per executed program (jit(<name>)...)
  host plane      "/host:CPU"; one line per thread; TraceAnnotation /
                  TraceMe events by name.  The program's ``Trace`` phases
                  appear as "Scheduling:<phase>".

All times are seconds on the profiler's own clock, which is shared by the
host and device planes.
"""

from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Sequence, Tuple

from . import stats

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
IDLE_LABEL = "(no program phase open)"


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def _events(line) -> List[Tuple[str, float, float]]:
    """(name, start s, end s) of a line's events."""
    out = []
    for ev in line.events:
        s = ev.start_ns * 1e-9
        out.append((ev.name, s, s + ev.duration_ns * 1e-9))
    return out


def planes(pd) -> Dict[str, Dict[str, List[Tuple[str, float, float]]]]:
    """plane name -> line name -> events.  Lines of one name are joined
    (a host plane has one line per thread, named by the thread)."""
    out: Dict[str, Dict[str, list]] = {}
    for plane in pd.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return out


def host_phases(tree, prefix: str) -> List[Tuple[str, float, float]]:
    """Host events whose name starts with ``prefix``, from every thread."""
    out = []
    for name, lines in tree.items():
        if not name.startswith(HOST_PLANE):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e[0].startswith(prefix))
    return sorted(out, key=lambda e: e[1])


def _attribute(gap: Tuple[float, float],
               phases: Sequence[Tuple[str, float, float]]
               ) -> Dict[str, float]:
    """Seconds of ``gap`` under each host phase; the rest is IDLE_LABEL."""
    g0, g1 = gap
    out: Dict[str, float] = {}
    covered = []
    for name, s, e in phases:
        if e <= g0 or s >= g1:
            continue
        lo, hi = max(s, g0), min(e, g1)
        out[name] = out.get(name, 0.0) + (hi - lo)
        covered.append((lo, hi))
    rest = (g1 - g0) - stats.union_seconds(covered)
    if rest > 1e-9:
        out[IDLE_LABEL] = rest
    return out


def summarize(pd, phase_prefix: str = "Scheduling:",
              top: int = 10) -> Dict[str, Any]:
    """The reduction.  Returns

      window_s     first to last event on any device plane
      busy_s       union of the device operations' intervals, averaged
                   over the device planes
      devices      per device plane: busy_s, n_ops
      ops          [[operation name, seconds]...] summed over devices,
                   the ``top`` largest
      modules      {program name: {"count": n, "seconds": s}} summed over
                   devices
      idle_gaps    [[host phase, seconds]...]: the device-idle time inside
                   the window by what the host was doing, the ``top``
                   largest; computed on the first device plane
    """
    tree = planes(pd)
    dev_names = sorted(n for n in tree if n.startswith(DEVICE_PREFIX))
    if not dev_names:
        raise ValueError(f"trace has no {DEVICE_PREFIX}* plane; planes: "
                         f"{sorted(tree)}")
    lo = min(e[1] for n in dev_names for evs in tree[n].values()
             for e in evs)
    hi = max(e[2] for n in dev_names for evs in tree[n].values()
             for e in evs)
    op_sums: Dict[str, float] = {}
    modules: Dict[str, Dict[str, float]] = {}
    devices = []
    busy_first: List[Tuple[float, float]] = []
    for i, name in enumerate(dev_names):
        ops = tree[name].get(OPS_LINE, [])
        ivals = stats.merged((s, e) for _, s, e in ops)
        if i == 0:
            busy_first = ivals
        devices.append({"plane": name, "n_ops": len(ops),
                        "busy_s": sum(e - s for s, e in ivals)})
        for op, s, e in ops:
            op_sums[op] = op_sums.get(op, 0.0) + (e - s)
        for mod, s, e in tree[name].get(MODULES_LINE, []):
            m = modules.setdefault(mod, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += e - s
    phases = host_phases(tree, phase_prefix)
    gaps: Dict[str, float] = {}
    edge = lo
    for s, e in busy_first + [(hi, hi)]:
        if s > edge:
            for k, v in _attribute((edge, s), phases).items():
                gaps[k] = gaps.get(k, 0.0) + v
        edge = max(edge, e)
    order = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {
        "window_s": hi - lo,
        "busy_s": sum(d["busy_s"] for d in devices) / len(devices),
        "devices": devices,
        "ops": [[k, v] for k, v in order(op_sums)],
        "modules": modules,
        "idle_gaps": [[k, v] for k, v in order(gaps)],
    }


def module_seconds(summary: Dict[str, Any], substring: str
                   ) -> Tuple[int, float]:
    """(executions, device seconds) of the programs whose name contains
    ``substring``."""
    n, s = 0, 0.0
    for name, m in summary["modules"].items():
        if substring in name:
            n += int(m["count"])
            s += m["seconds"]
    return n, s
