"""interpreter: CPU seconds of ALL live Python threads between two cycles' ends (cycle meta thread_cpu_s, summed over the thread names), mean a cycle, ms: the Python the host runs for a batch, whoever runs it.  Read it beside the cycle's period, the mean of meta thread_cpu_window_s: a sum near the period says the one interpreter is full."""
from perfbench.lib import threads


def read(ctx):
    return threads.thread_cpu_ms_per_cycle(ctx)
