"""commit and bind: share of the commit phase the serving thread was not running (1 - thread CPU / wall), %."""
from perfbench.lib import spans


def read(ctx):
    return spans.blocked_pct(ctx, "commit")
