"""compile: summed seconds of the compiles and cache loads inside the window's cycles, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.compile_stall_ms(ctx)
