"""kubeclose: the interprocedural compile-surface closure prover.

The fourth static-analysis layer (after kubelint, kubecensus and
kubeexact): an abstract interpretation over the HOST Python that tracks
the provenance of every value reaching a dispatch seam (the
``aot.dispatch``-seamed serving programs, raw ``jit`` roots) in a
shape-determining or static-arg position,
with a lattice over {const, bool, config-constant, registry-enumerated,
mesh-key, pad-capacity, pow2-bucketed, unbounded} propagated through
calls, returns, dataclass fields, and the scheduler's
``_prepare_group``/``_dispatch_group``/pipeline-ring plumbing.

From the proved-finite provenance it ENUMERATES the reachable signature
set of each seamed program at the committed north-star environment and
commits it as ``CLOSURE_MANIFEST.json``: every enumerated signature is
either covered by a kubecensus registry entry (and hence a
COMPILE_MANIFEST row and, for the seamed programs, an AOT_INDEX
artifact) or carried by a structured exemption naming its fallback
path.  An uncaptured-but-reachable signature is a cold-start compile
stall on the v5e run; a captured-but-unreachable row is a dead ladder
rung — both are findings.

The whole prover is pure AST + JSON: it never imports jax, so the full
proof (not just the committed-file ``--check``) runs in the no-jax CI
gate.  Rule family ``close/*``:

    close/unbounded-static          a static position whose provenance
                                    join is unbounded (not provably
                                    finite at north-star shapes)
    close/unbucketed-shape          a shape-derived static position that
                                    does not flow through pow2_bucket
                                    anywhere along its interprocedural
                                    dataflow
    close/uncaptured-signature      an enumerated reachable signature no
                                    registry entry covers and no
                                    exemption carries
    close/unreachable-manifest-row  a registry entry of a seamed program
                                    that no enumerated signature matches
    close/stale-exemption           a domains.py exemption that matches
                                    no finding (ages out, like
                                    kubeexact's)
"""
