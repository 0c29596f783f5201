"""interpreter: generation-2 collections that fell on the serving thread or the binder lane (span arg gc_full over the phases and bind-job), mean count a cycle."""
from perfbench.lib import threads


def read(ctx):
    return threads.gc_full_collections_per_cycle(ctx)
