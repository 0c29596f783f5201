"""prepare: the tensorize phase (the cluster delta build with its term refresh and apply, and the pod batch build), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "tensorize")
