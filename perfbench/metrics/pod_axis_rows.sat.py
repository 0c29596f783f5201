"""prepare: rows of the resident existing-pod axis the cycle dispatched in (cycle meta pod_bucket: the pow2 bucket, padding included), mean over the cycles that say."""
import statistics


def read(ctx):
    rows = [c["meta"]["pod_bucket"] for c in ctx.cycles
            if c["meta"].get("pod_bucket")]
    return statistics.fmean(rows) if rows else None
