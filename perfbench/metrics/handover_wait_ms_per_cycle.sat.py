"""commit and bind: the seconds the hand-over spent inside BindLane.submit, waiting for the job before it to be applied (commit arg handover_wait_s; part of submit_s), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "commit", "handover_wait_s")
