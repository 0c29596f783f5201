"""prepare: the tensorizer's term refresh (span delta-terms), per cycle that ran a delta build, ms; 0 where no term was dirty."""
from perfbench.lib import spans


def read(ctx):
    if not any(spans.named(c, "tensorize") for c in ctx.cycles):
        return None
    return spans.child_ms_per_cycle(ctx, "delta-terms", "delta-build")
