"""commit and bind: commit-time host re-check + Reserve + Permit, summed over the cycle's pods, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "commit", "reserve_s", "recheck_s",
                                  "permit_s")
