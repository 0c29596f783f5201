"""prepare: valid required node-selector terms of a cycle's incoming pods (span batch-build, arg rna_rows: the rows of rna_valid ops/kernels.py node_affinity_filter ORs over, counted after the class gather), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        if not c["meta"].get("auction_rounds"):
            continue
        builds = spans.named(c, "batch-build")
        if any("rna_rows" not in s["args"] for s in builds):
            return None       # a program that does not say
        if builds:
            per.append(sum(s["args"]["rna_rows"] for s in builds))
    return statistics.fmean(per) if per else None
