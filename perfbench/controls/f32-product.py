"""Control ``f32-product``: PodTopologySpread's raw score as the program
made it until PR 42, ``floor(sum of count * log(size + 2))`` in float32
with the device's own ``log``: the nearest precision below the float64
product that upstream truncates and that ``ops/kernels.py``
``log_weighted_floor`` makes exactly.  On a v5e it floors to another
integer than float64 at 16% of the (count, size) pairs
(``tests/test_spread_soft_product.py``).

It is NOT a control that a row of three zones over 5,000 nodes can fail,
and ``sp-prefspread-5000`` does not name it as its own: there a raw
score off by one moves the quotient of a zone that is not the least, and
no node of such a zone enters a tie set while the least zone holds a
node with one pod (it always does: 2,048 residents over 1,667 nodes a
zone).  ``perfbench/tools/cell_controls.py`` reads it beside the row's
own, so that PERF.md can say what check (b) cannot see."""

import contextlib

# what the reference's ``auction_schedule`` is called with
REFERENCE_KW = {"f32_product": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.ops import kernels
    real = kernels.log_weighted_floor

    def patched(cnt, size, counted, n_sizes):
        weight = jnp.log(size + 2.0)
        return jnp.floor(jnp.sum(
            jnp.where(counted, cnt * weight[..., None], 0.0), axis=-2))
    kernels.log_weighted_floor = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        kernels.log_weighted_floor = real
        jax.clear_caches()
