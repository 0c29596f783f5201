"""prepare: pod-list entries copied by the NodeInfo clones the cycle's snapshot update made (span snapshot: pods_copied), mean per cycle; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        snaps = spans.named(c, "snapshot")
        if not snaps:
            continue
        if any("pods_copied" not in s["args"] for s in snaps):
            return None       # a program that does not say what it copied
        per.append(sum(s["args"]["pods_copied"] for s in snaps))
    return statistics.fmean(per) if per else None
