"""prepare: cycle start to the end of tensorizing, flight recorder, ms."""
from perfbench.lib import readers


def read(ctx):
    return readers.prepare_ms_per_cycle(ctx)
