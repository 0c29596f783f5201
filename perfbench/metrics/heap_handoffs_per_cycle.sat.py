"""interpreter: the serving process's hand-offs of what survived to the collector's permanent generation (cycle meta heap_handoffs: those since the cycle before, 0 or 1), mean a cycle: 1/K while the policy engages; None for a program that does not say."""
import statistics


def read(ctx):
    said = [c["meta"]["heap_handoffs"] for c in ctx.cycles
            if "heap_handoffs" in c["meta"]]
    return statistics.fmean(said) if said else None
