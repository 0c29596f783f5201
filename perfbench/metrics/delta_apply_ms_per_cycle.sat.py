"""prepare: the dispatch of the cluster delta's scatter (span delta-apply, with the wholesale term upload where one ran), per cycle that ran one, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "delta-apply")
