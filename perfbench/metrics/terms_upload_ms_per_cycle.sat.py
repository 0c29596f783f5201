"""prepare: the wholesale replacement of the two existing-term tables on the device (span delta-terms-upload), per cycle that ran a delta build, ms; 0 where no term was dirty."""
from perfbench.lib import spans


def read(ctx):
    refreshed = [c for c in ctx.cycles if spans.named(c, "delta-terms")]
    if refreshed and not any(spans.named(c, "delta-terms-upload")
                             for c in refreshed):
        return None           # a program without the span
    if not any(spans.named(c, "tensorize") for c in ctx.cycles):
        return None
    return spans.child_ms_per_cycle(ctx, "delta-terms-upload", "delta-build")
