"""device programs: bytes of the resident cluster tensors' leaves (cycle meta cluster_device_bytes, summed from shapes), MB (1e6 bytes), mean over the cycles that say; None for a program that does not say."""
import statistics


def read(ctx):
    b = [c["meta"]["cluster_device_bytes"] for c in ctx.cycles
         if c["meta"].get("cluster_device_bytes")]
    return statistics.fmean(b) / 1e6 if b else None
