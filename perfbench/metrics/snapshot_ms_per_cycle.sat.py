"""prepare: the snapshot phase (cache.update_snapshot), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "snapshot")
