"""device programs: pods admitted, summed over a cycle's auction rounds, that the round-start rule would have held back: their node failed the round-start skew test of a DoNotSchedule constraint, or their pair's round-start room was used up at their turn (cycle meta spread_late_admits, the auction's own count in pod order), mean over the cycles that ran an auction with such a constraint; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    hard = [m for m in ran if m.get("spread_constraints")]
    if not hard or any("spread_late_admits" not in m for m in hard):
        return None
    return statistics.fmean(m["spread_late_admits"] for m in hard)
