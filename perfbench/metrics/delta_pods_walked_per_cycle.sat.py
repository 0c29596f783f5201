"""prepare: pods of dirty nodes the tensorizer's refresh visited (span delta-build: pods_walked), mean per cycle that ran a delta build; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        builds = spans.named(c, "delta-build")
        if not builds:
            continue
        if any("pods_walked" not in s["args"] for s in builds):
            return None       # a program that does not say what it walked
        per.append(sum(s["args"]["pods_walked"] for s in builds))
    return statistics.fmean(per) if per else None
