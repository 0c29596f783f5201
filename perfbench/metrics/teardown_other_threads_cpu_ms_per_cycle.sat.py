"""queue: the CPU seconds INSIDE the teardown of every Python thread but the serving thread and the binder lane (span teardown, arg thread_cpu_s): the client, the HTTP server, the periodic loops, per cycle, ms.  pop_teardown_ms_per_cycle.sat less this, teardown_serving_cpu_ and teardown_lane_cpu_ is the time no Python thread ran."""
from perfbench.lib import teardown


def read(ctx):
    return teardown.other_threads_cpu_ms_per_cycle(ctx)
