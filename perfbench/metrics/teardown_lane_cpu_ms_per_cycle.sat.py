"""queue: the binder lane's CPU seconds INSIDE the teardown (span teardown, arg thread_cpu_s, the entry binder-lane: a second reading of every thread's CPU clock at begin_pop's pick-up against the one Trace.finish took), per cycle, ms."""
from perfbench.lib import teardown


def read(ctx):
    return teardown.lane_cpu_ms_per_cycle(ctx)
