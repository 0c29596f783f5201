"""commit and bind: the pod copy + cache.assume_pod, summed over the cycle's pods, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "commit", "assume_s")
