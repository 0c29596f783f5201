"""Control ``bf16-scores``: the summed plugin scores held in bfloat16, the
nearest precision below the float32 the program sums them in: the step
that would halve the bytes of every [pods, nodes] score plane.  Put in
the program's place it must FAIL check (b)."""

import contextlib

# what the reference's ``auction_schedule`` is called with
REFERENCE_KW = {"lowprec": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.models import gang
    real = gang.run_scores

    def patched(*a, **kw):
        total, per_plugin = real(*a, **kw)
        return (total.astype(jnp.bfloat16).astype(jnp.float32), per_plugin)
    gang.run_scores = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        gang.run_scores = real
        jax.clear_caches()
