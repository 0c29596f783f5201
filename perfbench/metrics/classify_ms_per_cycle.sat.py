"""prepare: grouping the cycle's pods into classes and what is then made once a class ahead of the batch (spans classify: the grouping at the head of prefilter; the shared PodInfos and default spread selectors at the head of tensorize), summed, per cycle that has one, ms; None for a program that does not group."""
from perfbench.lib import spans


def read(ctx):
    return spans.span_ms_per_cycle(ctx, "classify")
