"""commit and bind: 95th percentile of (started - submitted) over the pods' bind rows, ms.  Since PR 27 (one bind job a cycle on one binder lane) that is a pod's position in a serial job plus the rest of the commit loop and the hand-over's wait, no longer a wait in a pool's queue (lib/spans.py)."""
from perfbench.lib import spans


def read(ctx):
    return spans.bind_queue_wait_p95_ms(ctx)
