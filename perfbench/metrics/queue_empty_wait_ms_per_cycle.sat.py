"""queue: time pop_batch spent blocked waiting for pods (pop.wait_s), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "pop", "wait_s")
