"""device programs (kernel): least time to move the rows that changed (kernels/delta_apply.py, from cycle meta delta_rows) / traced time of the cluster-delta scatter program per execution, %."""
import statistics

from perfbench.kernels import delta_apply, peaks
from perfbench.lib import spans, world, xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, delta_apply.DELTA_PROGRAM)
    ran = [c for c in ctx.cycles if c["meta"].get("delta_buckets")]
    if not n or s <= 0 or not ran:
        return None
    rows = statistics.fmean(c["meta"].get("delta_rows", 0) for c in ran)
    # how many of them are node rows, where every build says
    builds = [b["args"] for c in ran for b in spans.named(c, "delta-build")]
    node_rows = (sum(a["node_rows_dirty"] for a in builds) / len(ran)
                 if builds and all("node_rows_dirty" in a for a in builds)
                 else None)
    pk = peaks.peak(ctx.device["kind"])
    least = delta_apply.least_seconds(
        delta_rows=rows, flops_per_s=pk.flops_per_s,
        bytes_per_s=pk.bytes_per_s, node_rows=node_rows,
        **delta_apply.shapes_of(ctx.cell.config, world))
    return 100.0 * least["seconds"] / (s / n)
