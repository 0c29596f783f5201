"""device programs (kernel): least time for the rounds run WITH the incoming pods' preferred terms and the bound pods' own (kernels/auction.py + kernels/existing_terms.py + kernels/preferred_terms.py) / traced auction time, %; None for a row whose measured pods carry no preferred term."""
import statistics

from perfbench.kernels import existing_terms, peaks, preferred_terms
from perfbench.lib import readers, world, xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, readers.AUCTION_PROGRAM)
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    resident = int(ctx.cell.traffic["resident_bound"])
    preferred = preferred_terms.shapes_of(ctx.cell.config, ctx.n_nodes,
                                          resident, world)
    if not n or not ran or s <= 0 or not preferred["terms_per_pod"]:
        return None
    pk = peaks.peak(ctx.device["kind"])
    least = preferred_terms.least_seconds(
        batch=int(round(statistics.fmean(m.get("pods", 0) for m in ran))),
        nodes=ctx.n_nodes,
        rounds=statistics.fmean(m["auction_rounds"] for m in ran),
        flops_per_s=pk.flops_per_s, bytes_per_s=pk.bytes_per_s,
        bound_pods=ctx.resident_pods, preferred=preferred,
        existing=existing_terms.shapes_of(ctx.cell.config, ctx.n_nodes,
                                          resident, world))
    return 100.0 * least["seconds"] / (s / n)
