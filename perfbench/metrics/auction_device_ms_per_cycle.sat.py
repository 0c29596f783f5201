"""device programs: traced device time of the gang auction program per execution, ms."""
from perfbench.lib import readers


def read(ctx):
    return readers.auction_device_ms_per_cycle(ctx)
