"""interpreter: python_cpu_ms_per_cycle.sat without the cycle's serving thread and the binder lane: the client, the pools, the HTTP server, the periodic flushers, ms a cycle."""
from perfbench.lib import threads


def read(ctx):
    return threads.thread_cpu_ms_per_cycle(ctx, others_only=True)
