"""device programs: valid rows of the batch's own score-side terms (preferred affinity and anti-affinity, required affinity) the auction spliced into score_terms for its later rounds (cycle meta score_terms_spliced), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("score_terms_spliced" not in m for m in ran):
        return None
    return statistics.fmean(m["score_terms_spliced"] for m in ran)
