"""Control ``no-soft-spread``: PodTopologySpread's score at weight 0 (the
plugin's entry of ``ProgramConfig.scores`` rewritten where the auction
sums its rounds' scores), so that LeastAllocated and BalancedAllocation
alone decide a placement: the program as it would be if a
``ScheduleAnyway`` constraint were never scored.  Put in the program's
place it must FAIL check (b): the emptiest nodes lie in all three zones
alike, so about two placements in three fall outside the least zone's
tie set."""

import contextlib

PLUGIN = "PodTopologySpread"
# what the reference's ``auction_schedule`` is called with
REFERENCE_KW = {"no_soft_spread": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    from kubetpu.models import gang
    real = gang.run_scores

    def patched(cluster, batch, cfg, *a, **kw):
        scores = tuple((name, 0 if name == PLUGIN else weight)
                       for name, weight in cfg.scores)
        return real(cluster, batch, cfg._replace(scores=scores), *a, **kw)
    gang.run_scores = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        gang.run_scores = real
        jax.clear_caches()
