"""device programs: the batch's term sets whose existing-pod products the auction ran, not gated off (cycle meta term_sets_live, a list of names), mean count over the cycles that ran an auction; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("term_sets_live" not in m for m in ran):
        return None
    return statistics.fmean(len(m["term_sets_live"]) for m in ran)
