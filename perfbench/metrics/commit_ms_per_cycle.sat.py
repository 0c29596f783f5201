"""commit + bind: flight-recorder stage commit per cycle, ms."""
from perfbench.lib import readers


def read(ctx):
    return readers.stage_ms_per_cycle(ctx, "commit")
