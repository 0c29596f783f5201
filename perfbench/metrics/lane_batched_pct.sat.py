"""commit and bind: share of the bind jobs' rows whose binding cycle ran column-wise with the rest of their job (span bind-job, args batched / pods, written by the binder lane itself) over the window's jobs, %: 100 where no row waits on Permit, has a PreBind / PostBind plugin of its own or a binder that takes one pod at a time; None for a program that does not say."""
from perfbench.lib import spans, threads


def read(ctx):
    said = [s["args"] for c in ctx.cycles
            for s in spans.named(c, threads.JOB_SPAN)
            if "batched" in s["args"]]
    pods = sum(a["pods"] for a in said)
    return 100.0 * sum(a["batched"] for a in said) / pods if pods else None
