"""prepare: valid required pod-affinity term rows of a cycle's incoming pods (span batch-build, arg ra_rows: the rows ops/kernels.py interpod_filter's ra_live matches against the pod axis), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        if not c["meta"].get("auction_rounds"):
            continue
        builds = spans.named(c, "batch-build")
        if any("ra_rows" not in s["args"] for s in builds):
            return None       # a program that does not say
        if builds:
            per.append(sum(s["args"]["ra_rows"] for s in builds))
    return statistics.fmean(per) if per else None
