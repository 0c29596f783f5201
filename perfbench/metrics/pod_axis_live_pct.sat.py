"""prepare: share of the resident existing-pod axis that holds a pod (cycle meta pod_rows_live / pod_bucket), %, mean over the cycles that say both; None for a program that does not say."""
import statistics


def read(ctx):
    metas = [c["meta"] for c in ctx.cycles if c["meta"].get("pod_bucket")]
    if not metas or any("pod_rows_live" not in m for m in metas):
        return None
    return statistics.fmean(100.0 * m["pod_rows_live"] / m["pod_bucket"]
                            for m in metas)
