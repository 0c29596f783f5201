"""device programs: pods admitted, summed over a cycle's auction rounds, through InterPodAffinity's self-match bootstrap alone (filtering.go:356: their required affinity terms matched no pod anywhere as their round started; cycle meta affinity_bootstrap_admits, the auction's own count), mean over the cycles that ran an auction with a required affinity term; None for a program that does not say."""
import statistics


def read(ctx):
    ran = [c["meta"] for c in ctx.cycles if c["meta"].get("auction_rounds")]
    hard = [m for m in ran if m.get("required_affinity_terms")]
    if not hard or any("affinity_bootstrap_admits" not in m for m in hard):
        return None
    return statistics.fmean(m["affinity_bootstrap_admits"] for m in hard)
