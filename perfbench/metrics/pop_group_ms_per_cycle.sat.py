"""queue: the skip check a popped pod (one store.get_pod and one cache.is_assumed_pod each) and the grouping by profile (the pop span's group_s), per cycle, ms."""
from perfbench.lib import spans


def read(ctx):
    return spans.arg_ms_per_cycle(ctx, "pop", "group_s")
