"""Control ``blind-batch``: the batch's own pods left out of the
required-term filter (the auction's ``intra_batch_topology`` switched
off): the exchange between the pods of one batch left out.  Put in the
program's place it must FAIL check (b)."""

import contextlib

# what the reference's ``auction_schedule`` is called with
REFERENCE_KW = {"blind_batch": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    from kubetpu.models import gang
    real = gang.run_auction

    def patched(*a, **kw):
        kw["intra_batch_topology"] = False
        return real(*a, **kw)
    gang.run_auction = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        gang.run_auction = real
        jax.clear_caches()
