"""device programs (kernel): least time for the rounds run WITH the batch's ScheduleAnyway constraints' score (kernels/auction.py + kernels/spread_soft.py) / traced auction time, %; None for a row whose measured pods carry no ScheduleAnyway constraint."""
import statistics

from perfbench.kernels import peaks, spread_soft
from perfbench.lib import readers, world, xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, readers.AUCTION_PROGRAM)
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    shapes = spread_soft.shapes_of(ctx.cell.config, world)
    if not n or not ran or s <= 0 or not shapes["constraints_per_pod"]:
        return None
    pk = peaks.peak(ctx.device["kind"])
    least = spread_soft.least_seconds(
        batch=int(round(statistics.fmean(m.get("pods", 0) for m in ran))),
        nodes=ctx.n_nodes,
        rounds=statistics.fmean(m["auction_rounds"] for m in ran),
        flops_per_s=pk.flops_per_s, bytes_per_s=pk.bytes_per_s,
        resident_pods=ctx.resident_pods, **shapes)
    return 100.0 * least["seconds"] / (s / n)
