"""queue: span heap-boundary, the heap policy's boundary between two cycles (one young pass and the hand-off to the permanent generation, or a sweep), per cycle, ms."""
from perfbench.lib import spans, teardown


def read(ctx):
    return spans.span_ms_per_cycle(ctx, teardown.HEAP_SPAN)
