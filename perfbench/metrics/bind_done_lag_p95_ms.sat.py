"""commit and bind: 95th percentile of (bind done - end of the cycle's readback), ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.bind_done_lag_p95_ms(ctx)
