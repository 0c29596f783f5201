"""device programs (kernel): least time for the rounds run / traced auction time, %."""
from perfbench.lib import readers


def read(ctx):
    return readers.auction_roofline_pct(ctx)
