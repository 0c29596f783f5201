"""commit and bind: 95th percentile of a bind's wait in the pool (started - submitted), ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.bind_queue_wait_p95_ms(ctx)
