"""Control ``no-batch-score-terms``: the batch's own score-side terms left
out of the auction's later rounds (``ProgramConfig.batch_score_sets``
empty, so ``models/gang.py`` ``_extend_cluster`` splices nothing into
``score_terms``): a pod admitted in round r is counted by a later pod's
OWN preferred term (it is on the pod axis) but its terms do not count
the later pod, 2c + k where upstream's serial loop reads 2c + 2k.  It is
the program as it was before PR 40.

The reference's counterpart is ``perfbench/reference/batch_blind_terms.py``
(``interpod_terms`` with the owners of pods admitted inside the auction
left out), run by ``perfbench/tools/batch_terms_control.py``; it is NOT
a configuration's control, because on some seeds it reads 0.  Every pod
of ``sp-prefaffinity-5000`` is alike, so the two counts order the nodes
alike and part only where a round leaves nodes of its tie set PART full
and sends pods on.  The auction's residual window does that: after the
first round 512 pods propose a round, so a tie set of 42 nodes takes a
dozen pods a node and the pods behind the window meet nodes of unequal
k (seed 40001: 51 of 1,024 outside every tie set, each by one point of
score).  Where every tie-set node fills before the next round looks
(tie sets of 1, 5 and 35 nodes under 1,024, 990 and 815 proposals: seed
1) the half count has nothing to misjudge and the control reads 0
(PERF.md, section 4).  Its sure guard is the hand-worked case of
``tests/perfbench/test_perfbench_prefaffinity.py``."""

import contextlib

# what ``batch_blind_terms.auction_schedule`` is called with
REFERENCE_KW = {"no_batch_score_terms": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    from kubetpu import scheduler
    real = getattr(scheduler, "batch_score_sets", None)
    if real is None:            # a program without the splice: itself
        yield
        return
    scheduler.batch_score_sets = lambda *a, **kw: ()
    jax.clear_caches()
    try:
        yield
    finally:
        scheduler.batch_score_sets = real
        jax.clear_caches()
