"""queue: span teardown-release, from the pop phase's opening to the serving loop having dropped the cycle's outcomes (the frames' unwinding, the frees of the prepared cycle, the states and the outcomes), per cycle, ms."""
from perfbench.lib import spans, teardown


def read(ctx):
    return spans.span_ms_per_cycle(ctx, teardown.RELEASE_SPAN)
