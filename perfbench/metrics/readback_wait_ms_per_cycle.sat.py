"""readback: device_wait_s of the packed-readback stage per cycle, ms."""
from perfbench.lib import readers


def read(ctx):
    return readers.readback_wait_ms_per_cycle(ctx)
