"""device programs: pods a cycle's auction bound (rows of its bind table handed to a binder) / its rounds (meta auction_rounds), mean over the cycles that ran a round."""
import statistics


def read(ctx):
    per = []
    for c in ctx.cycles:
        rounds = c["meta"].get("auction_rounds")
        if rounds and "binds" in c:
            per.append(sum(1 for row in c["binds"] if row[0] > 0.0) / rounds)
    return statistics.fmean(per) if per else None
