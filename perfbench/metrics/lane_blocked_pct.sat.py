"""commit and bind: share of the bind jobs' extent the thread that ran them was not running (1 - thread CPU / wall over the window's bind-job spans): waiting for the interpreter, a lock or a wake-up, %."""
from perfbench.lib import spans, threads


def read(ctx):
    return spans.blocked_pct(ctx, threads.JOB_SPAN)
