"""queue: the pop phase (pop_batch, the per-pod skip check, grouping) less its wait on an empty queue, per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "pop", minus_arg="wait_s")
