"""prepare: the prefilter phase (PreFilter over the batch's pods), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "prefilter")
