#!/usr/bin/env python3
"""Print what a ``*.xplane.pb`` holds: planes, lines, event counts and the
most frequent event names per line.  Look at one trace by hand before
trusting the reduction in perfbench/lib/xplane.py."""

from __future__ import annotations

import collections
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from perfbench.lib import xplane
    path = argv[1]
    if os.path.isdir(path):
        path = xplane.find_trace(path)
    pd = xplane.load(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            if not evs:
                continue
            dur = sum(e.duration_ns for e in evs) * 1e-9
            lo = min(e.start_ns for e in evs) * 1e-9
            hi = max(e.start_ns + e.duration_ns for e in evs) * 1e-9
            print(f"  line {line.name!r}: {len(evs)} events, sum {dur:.6f} s,"
                  f" span {lo:.6f}..{hi:.6f}")
            names = collections.Counter()
            secs = collections.Counter()
            for e in evs:
                names[e.name] += 1
                secs[e.name] += e.duration_ns * 1e-9
            for name, s in secs.most_common(8):
                print(f"      {s:.6f} s  x{names[name]}  {name[:100]}")
    print(xplane.summarize(pd) if any(
        p.name.startswith(xplane.DEVICE_PREFIX) for p in pd.planes)
        else "no device plane")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
