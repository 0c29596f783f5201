"""queue: the serving thread's own CPU seconds inside the teardown the pop phase opens with (span teardown, arg cpu_s); the teardown's extent (pop_teardown_ms_per_cycle.sat) less this is time it did not run, per cycle, ms."""
from perfbench.lib import spans, teardown


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, teardown.SPAN, "cpu_s")
