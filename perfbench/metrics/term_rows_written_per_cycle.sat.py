"""prepare: existing-term rows the tensorizer's update wrote in its mirror (span delta-terms: rows_written, rows tombstoned plus rows appended), mean per cycle that ran a delta build; 0 for a cycle in which no owner came or went; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        if not spans.named(c, "delta-build"):
            continue
        updates = spans.named(c, "delta-terms")
        if any("rows_written" not in s["args"] for s in updates):
            return None       # a program that rebuilds the tables whole
        per.append(sum(s["args"]["rows_written"] for s in updates))
    return statistics.fmean(per) if per else None
