"""device programs: propose-and-admit rounds the gang auction ran (cycle meta auction_rounds), mean over the cycles that ran an auction."""
import statistics


def read(ctx):
    rounds = [c["meta"].get("auction_rounds") for c in ctx.cycles]
    rounds = [r for r in rounds if r]
    return statistics.fmean(rounds) if rounds else None
