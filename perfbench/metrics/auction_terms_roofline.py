"""device programs (kernel): least time for the rounds run WITH the existing pods' terms (kernels/auction.py + kernels/existing_terms.py) / traced auction time, %."""
import statistics

from perfbench.kernels import existing_terms, peaks
from perfbench.lib import readers, world, xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, readers.AUCTION_PROGRAM)
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not n or not ran or s <= 0:
        return None
    config = ctx.cell.config
    batch = int(round(statistics.fmean(m.get("pods", 0) for m in ran)))
    shapes = existing_terms.shapes_of(
        config, ctx.n_nodes, int(ctx.cell.traffic["resident_bound"]), world)
    measured = world.measured_record(config, "measured", 0)
    pk = peaks.peak(ctx.device["kind"])
    least = existing_terms.least_seconds(
        batch=batch, nodes=ctx.n_nodes,
        rounds=statistics.fmean(m["auction_rounds"] for m in ran),
        flops_per_s=pk.flops_per_s, bytes_per_s=pk.bytes_per_s,
        resident_pods=ctx.resident_pods,
        incoming_terms=any(getattr(measured, f) for f in existing_terms.OWNED),
        term_rows=int(shapes["term_rows"]),
        labels_per_term=shapes["labels_per_term"],
        matched_node_adds=batch * shapes["matched_node_adds_per_pod"])
    return 100.0 * least["seconds"] / (s / n)
