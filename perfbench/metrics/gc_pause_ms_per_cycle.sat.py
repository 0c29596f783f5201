"""interpreter: the garbage collector's pauses (span arg gc_s over the cycle's phases and its bind-job, plus cycle meta gc_other_s for the threads with neither open), mean a cycle, ms."""
from perfbench.lib import threads


def read(ctx):
    return threads.gc_pause_ms_per_cycle(ctx)
