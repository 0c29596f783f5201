"""prepare: unique valid selector rows compiled for a cycle's required node-selector terms (cycle meta node_affinity_unique_selectors: the U of the [U, Q, L] x [N, L] match; 1 where every pod states the same term, the batch size where the dedup broke), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("node_affinity_unique_selectors" not in m
                      for m in ran):
        return None
    return statistics.fmean(m["node_affinity_unique_selectors"]
                            for m in ran)
