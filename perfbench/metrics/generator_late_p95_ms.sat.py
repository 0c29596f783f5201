"""client: 95th percentile of (replacement offered - bind seen), ms."""
from perfbench.lib import readers


def read(ctx):
    return readers.replace_late_p95_ms(ctx)
