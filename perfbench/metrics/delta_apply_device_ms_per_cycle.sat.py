"""device programs: traced device time of the cluster-delta scatter program (*apply_cluster_delta*) per execution, ms."""
from perfbench.kernels.delta_apply import DELTA_PROGRAM
from perfbench.lib import xplane


def read(ctx):
    n, s = xplane.module_seconds(ctx.trace, DELTA_PROGRAM)
    return 1e3 * s / n if n else None
