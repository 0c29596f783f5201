"""commit and bind: the bind pool's submit + the pruning of the in-flight list, summed over the cycle's pods, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "commit", "submit_s")
