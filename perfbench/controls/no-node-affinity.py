"""Control ``no-node-affinity``: every node satisfies the pod's REQUIRED
node-affinity terms (``ops/kernels.node_affinity_filter`` handed the
batch with ``has_rna`` all False, so ``rna_ok`` is all True): the program
as it would be if NodeAffinity's required terms admitted everything.
``spec.nodeSelector`` (the filter's other half) and every other filter
and score stay as they are.

It is NOT a control that ``sp-nodeaffinity-5000``'s own check (b) can
fail, and the row does not name it as its own: upstream labels every
node ``zone1`` and the term lists ``zone1``, so the real filter admits
every node too and the control changes nothing: it reads 0 in that row
BY CONSTRUCTION.  Where it bites: the row's nodes in three zones of
which the term lists two and the init pods fill two, so the third is
EMPTY, which LeastAllocated prefers and the filter refuses; there it
sends the whole batch to the refused zone:
``perfbench/tools/nodeaffinity_zones_check.py`` at the row's size,
``tests/test_node_affinity_zones.py`` in small.
``perfbench/tools/cell_controls.py`` reads it beside the row's own
(``bf16-scores``), so that PERF.md can say what the cell cannot see."""

import contextlib

# what the reference's ``auction_schedule`` is called with
# (``perfbench/reference/node_affinity.py``)
REFERENCE_KW = {"no_node_affinity": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.ops import kernels
    real = kernels.node_affinity_filter

    def patched(cluster, batch, *a, **kw):
        return real(cluster, batch._replace(
            has_rna=jnp.zeros_like(batch.has_rna)), *a, **kw)
    kernels.node_affinity_filter = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        kernels.node_affinity_filter = real
        jax.clear_caches()
