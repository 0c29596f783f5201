"""device programs: after a cycle's admits, the most less the least of the pods that the batch's first ScheduleAnyway constraint's selector matches over the pairs of its key (cycle meta spread_soft_skew, the auction's own count by pair id), mean over the cycles that ran an auction with such a constraint; None for a program that does not say.  A QUALITY counter: no guarantee bounds it (ScheduleAnyway promises no skew) and it moves no rate; BENCHMARK.json files it under pods_bound_per_s because a metric has to name one."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    if not ran or any("spread_soft_constraints" not in m for m in ran):
        return None
    soft = [m for m in ran if m["spread_soft_constraints"]]
    if not soft or any("spread_soft_skew" not in m for m in soft):
        return None
    return statistics.fmean(m["spread_soft_skew"] for m in soft)
