"""device programs: proposals, summed over a cycle's auction rounds, that found their node full at their turn and went to the next round (cycle meta capacity_deferred, the auction's own count), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("capacity_deferred" not in m for m in ran):
        return None
    return statistics.fmean(m["capacity_deferred"] for m in ran)
