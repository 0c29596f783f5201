"""commit and bind: share of the cycles' placed pods that the commit loop committed as part of a RUN, a step at a time over the run (span commit, args batched / pods, written by the loop itself) over the window's cycles, %: 100 where no pod has a host filter to re-check or a Reserve / Unreserve / Permit plugin of its own and the binds ride the lane; None for a program that does not say."""
from perfbench.lib import spans


def read(ctx):
    said = [s["args"] for c in ctx.cycles for s in spans.named(c, "commit")
            if "batched" in s["args"]]
    pods = sum(a["pods"] for a in said)
    return 100.0 * sum(a["batched"] for a in said) / pods if pods else None
