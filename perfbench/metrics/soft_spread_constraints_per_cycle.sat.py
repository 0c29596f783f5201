"""prepare: valid ScheduleAnyway constraint rows in a cycle's batch (cycle meta spread_soft_constraints), mean over the cycles that ran an auction; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("spread_soft_constraints" not in m for m in ran):
        return None
    return statistics.fmean(m["spread_soft_constraints"] for m in ran)
