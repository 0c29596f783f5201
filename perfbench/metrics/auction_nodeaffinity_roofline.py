"""device programs (kernel): least time for ONE cycle's auction WITH the pods' required node-affinity terms, counted once a cycle whatever the rounds (kernels/auction.py for one round + kernels/node_affinity.py) / traced auction time, %; None for a row whose measured pods carry no required node-affinity term."""
import statistics

from perfbench.kernels import node_affinity, peaks
from perfbench.lib import readers, world, xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, readers.AUCTION_PROGRAM)
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    shapes = node_affinity.shapes_of(ctx.cell.config, world)
    if not n or not ran or s <= 0 or not shapes["values_per_pod"]:
        return None
    pk = peaks.peak(ctx.device["kind"])
    least = node_affinity.least_seconds(
        batch=int(round(statistics.fmean(m.get("pods", 0) for m in ran))),
        nodes=ctx.n_nodes, flops_per_s=pk.flops_per_s,
        bytes_per_s=pk.bytes_per_s, shapes=shapes)
    return 100.0 * least["seconds"] / (s / n)
