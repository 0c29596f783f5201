"""prepare: the host half of the cluster delta (span delta-build: the dirty scan, the mirror rows refilled, the owner check, the gather of the update tables), per cycle that ran one, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "delta-build")
