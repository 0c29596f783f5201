"""prepare: the copy of the tensorizer's pod-row map and uid list the cycle keeps, with the journal seam (span row-maps, a child of tensorize), per cycle that has one, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "row-maps")
