"""Control ``no-required-affinity``: every node satisfies the INCOMING
pod's required pod-affinity terms (``ops/kernels.interpod_filter`` handed
the batch with no valid ``ra`` row, and its "matches nothing anywhere"
verdict, which drives the auction's bootstrap deferral, all False): the
program as it would be if ``ra_live`` admitted everything.  The batch's
own score rows are still spliced, the existing pods' terms and the pod's
required anti-affinity still filter.

It is NOT a control that ``sp-podaffinity-5000``'s own check (b) can
fail, and the row does not name it as its own: upstream labels every
node ``zone1``, so once one blue pod is bound the real filter admits
every node too, and the control changes nothing (it reads 0).  Where
blue pods that carry the LABEL and no term live in ONE of three zones it
sends the batch to the two empty zones, which the filter refuses; where
they OWN the term their score rows (hardPodAffinityWeight 1) hold the
zone alone and it reads 0 again: ``perfbench/tools/zone_affinity_check.py``
at the row's size, ``tests/test_required_affinity_zone.py`` in small.
``perfbench/tools/cell_controls.py`` reads it beside the row's own
(``bf16-scores``), so that PERF.md can say what the cell cannot see."""

import contextlib

# what the reference's ``auction_schedule`` is called with
# (``perfbench/reference/interpod_required.py``)
REFERENCE_KW = {"no_required_affinity": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.ops import kernels
    real = kernels.interpod_filter

    def patched(cluster, batch, *a, **kw):
        ra = batch.ra._replace(valid=jnp.zeros_like(batch.ra.valid))
        out = real(cluster, batch._replace(ra=ra), *a, **kw)
        if kw.get("return_no_matches"):
            ok, unres, no_matches = out
            return ok, unres, jnp.zeros_like(no_matches)
        return out
    kernels.interpod_filter = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        kernels.interpod_filter = real
        jax.clear_caches()
