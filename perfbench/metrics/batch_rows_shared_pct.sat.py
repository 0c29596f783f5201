"""prepare: the share of the batch's rows that were not built but gathered from the row of their pod CLASS's representative (span batch-build: 100 x (pods - rows_built) / pods), mean over the cycles that built a batch; 0 where every pod was built a row of its own; None for a program that does not say."""
import statistics

from perfbench.lib import spans


def read(ctx):
    per = []
    for c in ctx.cycles:
        for s in spans.named(c, "batch-build"):
            pods, built = s["args"].get("pods"), s["args"].get("rows_built")
            if built is None:
                return None       # a program that builds a row a pod
            if pods:
                per.append(100.0 * (pods - built) / pods)
    return statistics.fmean(per) if per else None
