"""prepare: rows of the tensorizer's host mirror rewritten from their NodeInfo / PodInfo (span delta-build: node_rows_refilled + pod_rows_refilled), mean per cycle that ran a delta build; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        builds = spans.named(c, "delta-build")
        if not builds:
            continue
        if any("pod_rows_refilled" not in s["args"] for s in builds):
            return None       # a program that does not say what it refilled
        per.append(sum(s["args"]["node_rows_refilled"]
                       + s["args"]["pod_rows_refilled"] for s in builds))
    return statistics.fmean(per) if per else None
