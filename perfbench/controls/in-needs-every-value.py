"""Control ``in-needs-every-value``: a node-selector term's ``In``
requirement read as "the node carries EVERY listed value" where upstream
reads "one of them" (``labels.Selector``: ``set.Has(ls.Get(key))``).  A
node carries one value a key, so a requirement that lists two distinct
values is met by no node: ``ops/selectors._reqs_of`` hands such a
requirement on with no value left to match (the compiled row's
``vals_hot`` stays empty; a listed value no node carries is not in the
vocabulary and could not be counted on the device).  Requirements that
list one value, other operators, label selectors of pods and
``spec.nodeSelector`` stay as they are.

It is a control ``sp-nodeaffinity-5000``'s own check (b) CAN fail, and
the one that shows the check sees a node wrongly REFUSED: the row's term
lists ``zone1`` and ``zone2``, every node is refused, and all 1,024 pods
of the sample read "left pending, the reference can place it".  The
row's named ``control`` stays ``bf16-scores`` (the nearest lower
precision); ``perfbench/tools/cell_controls.py`` reads this one beside
it."""

import contextlib

# what the reference's ``auction_schedule`` is called with
# (``perfbench/reference/node_affinity.py``)
REFERENCE_KW = {"in_needs_every_value": True}


@contextlib.contextmanager
def program_control():
    """The program with the control patched in, for the block.  The
    patch is on the host's compile of a selector into arrays: no traced
    program changes, so nothing warm has to be dropped."""
    from kubetpu.api import types as api
    from kubetpu.ops import selectors
    real = selectors._reqs_of

    def patched(sel):
        reqs = real(sel)
        if reqs is None or not isinstance(sel, api.NodeSelectorTerm):
            return reqs
        return [r._replace(values=[])
                if r.op == "In" and len(set(r.values)) > 1 else r
                for r in reqs]
    selectors._reqs_of = patched
    try:
        yield
    finally:
        selectors._reqs_of = real
