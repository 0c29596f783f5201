"""commit and bind: 95th percentile of (bind done - end of the cycle's readback), ms.  Since PR 27 the cycle's binds run as one serial job after the commit loop, so this is the loop + the hand-over + the pod's position in the job (lib/spans.py)."""
from perfbench.lib import spans


def read(ctx):
    return spans.bind_done_lag_p95_ms(ctx)
