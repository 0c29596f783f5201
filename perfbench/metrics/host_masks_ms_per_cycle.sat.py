"""prepare: the host-masks phase (host-side filter masks for the batch), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "host-masks")
