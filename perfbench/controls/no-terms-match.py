"""Control ``no-terms-match``: no existing-pod term reported as selecting
any pod of the batch (``ops/kernels.existing_terms_match``, which feeds
InterPodAffinity's filter and score contractions, all False): a selector
or namespace match that fails to select.  It is the control of
``tools/mixed_sample_check.py`` (``--control no-terms-match``), not a
configuration's: put in the program's place it FAILS check (b) on a
sample that existing terms do select (a pod the required anti-affinity
of an existing pod excludes from a node is placed there).  On a sample
no existing term selects (plain pods: the harness's own check (b) in
``sp-mixed-5000``) it changes nothing and reads 0, so that cell names
``bf16-scores``: PERF.md, sections 4 and 7."""

import contextlib

# what the reference's ``auction_schedule`` is called with
REFERENCE_KW = {"no_terms_match": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block."""
    import jax
    import jax.numpy as jnp
    from kubetpu.ops import kernels
    real = kernels.existing_terms_match

    def patched(terms, batch):
        return jnp.zeros_like(real(terms, batch))
    kernels.existing_terms_match = patched
    jax.clear_caches()        # the auction is traced anew, patched
    try:
        yield
    finally:
        kernels.existing_terms_match = real
        jax.clear_caches()
