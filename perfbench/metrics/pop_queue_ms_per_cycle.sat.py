"""queue: pop_batch's own work (the pop span's queue_s less its wait_s on an empty queue), per cycle, ms."""
from perfbench.lib import teardown


def read(ctx):
    return teardown.pop_queue_ms_per_cycle(ctx)
