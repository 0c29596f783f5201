"""prepare: the pod batch's tensors (span batch-build, inside tensorize beside the cluster delta), per cycle that built one, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "batch-build")
