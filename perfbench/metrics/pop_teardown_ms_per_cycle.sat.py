"""queue: the previous cycle's teardown, which the pop phase opens with (its arg teardown_s), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "pop", "teardown_s")
