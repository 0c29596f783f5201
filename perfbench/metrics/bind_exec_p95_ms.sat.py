"""commit and bind: 95th percentile of a bind's run time (done - started), ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.bind_exec_p95_ms(ctx)
