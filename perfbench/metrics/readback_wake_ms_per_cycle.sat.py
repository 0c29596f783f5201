"""readback: end of the auction's last device operation to the serving thread's return from the readback, per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.readback_wake_ms_per_cycle(ctx)
