"""commit and bind: the CPU seconds the thread that ran the cycle's bind job spent on it (span bind-job, arg cpu_s, written by the binder lane itself), per cycle that has one, ms; beside lane_busy_ms_per_cycle.sat it tells the lane's work from its waiting."""
from perfbench.lib import spans, threads


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, threads.JOB_SPAN, "cpu_s")
