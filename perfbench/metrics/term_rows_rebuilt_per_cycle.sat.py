"""prepare: existing-term rows the tensorizer's refresh (span delta-terms: filter_rows + score_rows) recompiled, mean per cycle that ran a delta build; 0 for a cycle whose terms were not dirty."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        if not spans.named(c, "delta-build"):
            continue
        refreshes = spans.named(c, "delta-terms")
        if any("filter_rows" not in s["args"] for s in refreshes):
            return None       # a program that does not say what it rebuilt
        per.append(sum(s["args"]["filter_rows"] + s["args"]["score_rows"]
                       for s in refreshes))
    return statistics.fmean(per) if per else None
