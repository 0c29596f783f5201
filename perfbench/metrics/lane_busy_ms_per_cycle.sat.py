"""commit and bind: the wall extent of the cycle's one bind job on the binder lane (last done - first started over its rows), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.lane_busy_ms_per_cycle(ctx)
