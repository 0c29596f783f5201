#!/bin/sh
# CI lint gate: kubelint in JSON mode, nonzero exit on any unsuppressed
# finding.  Covers all seven rule families — host-sync, recompile,
# numeric, purity, exact (raw lax collectives / raw tie-argmax must
# route through the blessed ops/kernels.py helpers so tools/kubeexact
# can prove the reduction surface),
# concurrency (lock discipline for the threaded host path,
# including the flight-recorder classes: utils/trace.py FlightRecorder /
# CycleRecord and utils/decisions.py DecisionLog are guarded-by annotated
# and must stay tree-clean), and delta (incremental-tensorization
# discipline: no full re-tensorize/device_put reachable from the cycle
# loop outside the blessed DeltaTensorizer resync path).  Builders run
# this by default via `make lint`; the same check gates tier-1 through
# tests/test_kubelint.py::test_kubetpu_tree_is_clean.
set -e
cd "$(dirname "$0")/.."
python -m tools.kubelint kubetpu/ --json
# explicit concurrency-family pass over the observability layer: the new
# lock-guarded recorder/audit classes must be clean on their own, so a
# future refactor can't hide a violation behind an unrelated suppression.
# The chaos registry rides the same pass: its fire counters are
# guarded-by annotated and its decide/act split must never sleep or
# raise under the lock (blocking-under-lock).
# The depth-k pipelined executor (kubetpu/pipeline.py) joins it too: its
# in-flight ring is guarded-by annotated, and no device dispatch,
# readback or sleep may ever run under the ring lock.  The durable cycle
# journal (utils/journal.py) joins it: its file-index/counter state is
# guarded-by annotated and record I/O runs outside the lock
# The shard_map mesh module (kubetpu/parallel/shardmap.py) joins it:
# its trace-time Mesh registry is guarded-by annotated and read only at
# trace time (never under a traced computation)
python -m tools.kubelint kubetpu/utils/trace.py kubetpu/utils/decisions.py \
	kubetpu/utils/chaos.py kubetpu/pipeline.py \
	kubetpu/utils/journal.py kubetpu/parallel/shardmap.py \
	--rules concurrency --json
# explicit delta-family pass over the serving loop: the cycle path must
# stay scatter-only (full-retensorize-in-loop), independent of any
# unrelated suppression elsewhere in the tree.  The pipelined executor
# rides along — its drain is the cycle loop now.  journal.py rides too:
# it reads the resident mirror at commit and must never re-tensorize
# parallel/shardmap.py rides the delta pass too: the mesh dispatch
# wrappers sit on the cycle path and must never re-tensorize or
# re-device_put the resident cluster outside the blessed seams
python -m tools.kubelint kubetpu/scheduler.py kubetpu/pipeline.py \
	kubetpu/utils/journal.py kubetpu/parallel/shardmap.py \
	--rules delta --json
# compile-surface census (tools/kubecensus): jaxpr-level abstract
# interpretation of every jit root.  Fails on (a) any unsuppressed
# census finding — donation-unconsumed, f64-promotion, host-callback,
# rank-promotion, constant-capture, unregistered-root — and (b) DRIFT
# against the committed COMPILE_MANIFEST.json in either direction: a
# traced variant the manifest lacks, or a committed row no trace
# reproduces (a dead ladder bucket).  Regenerate after an intentional
# surface change: make census (python -m tools.kubecensus --write).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.kubecensus --check --json
# AOT artifact index gate (tools/kubeaot --check, pure JSON, no jax):
# the committed AOT_INDEX.json and COMPILE_MANIFEST.json must share the
# same census-family row keys in BOTH directions — an artifact with no
# manifest row, or a manifest row with no artifact at census rungs,
# fails.  Regenerate after an intentional surface change: make aot.
python -m tools.kubeaot --check --json
# Compile-surface closure gate, pure-JSON half (tools/kubeclose --check,
# no jax): the committed CLOSURE_MANIFEST.json must carry zero findings
# and zero unbounded axes, pin the northstar environment byte-equal to
# tools/kubeexact/northstar.py, resolve every registry coverage pointer
# to a COMPILE_MANIFEST.json row, give every exempt combo a reason
# naming its fallback path, and cover every AOT_INDEX.json program.
python -m tools.kubeclose --check --json
# Compile-surface closure, full prover (still no jax — pure AST over
# kubetpu/): re-proves the closure interprocedurally, enumerates every
# reachable dispatch signature at the committed north-star environment,
# and fails on any close/* finding (unbounded-static, unbucketed-shape,
# uncaptured-signature, unreachable-manifest-row, stale-exemption) or
# DRIFT against the committed CLOSURE_MANIFEST.json in either direction.
# Regenerate after an intentional seam change: make close.
python -m tools.kubeclose --json
# Exactness manifest gate, pure-JSON half (tools/kubeexact --check, no
# jax): the committed EXACT_MANIFEST.json must pin the northstar
# environment and constants, keep every proof exact/exempt with margin
# above the 4x floor, and name only programs COMPILE_MANIFEST.json
# licenses.
python -m tools.kubeexact --check --json
# Pod-axis mesh scale-out (kubetpu/parallel/shardmap.py): the explicit
# shard_map auction/scan vs the single-device oracle on the 8-virtual-CPU
# mesh — sharded-vs-unsharded bit-identity at the previously env-gated
# (2,4)/(4,2) shapes (tiled + replicated surfaces, windowed rounds, the
# serving path with the double-buffered batch upload and the pre-sharded
# delta scatter).  The legacy gspmd lowering's (2,4) case is asserted
# too: it passes on the installed jax.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_mesh.py -q -m 'not slow' -p no:cacheprovider
# Chaos harness + self-healing runtime (utils/chaos.py): every named
# injection point's seeded recovery scenario — serving thread alive, no
# lost pods, no double binds, mirror/device fingerprint match after
# induced faults — and the disarmed-no-op poison test (a disarmed run
# adds zero locks and zero readbacks to the hot path).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_chaos.py -q -m 'not slow' -p no:cacheprovider
# Depth-k pipelined executor (kubetpu/pipeline.py): depth-parity
# placement goldens (depth 1 == 2 == 4 bit-identical), the
# gather-window/free-slot gate, per-slot exemption accounting, ring-slot
# flight tags, and the flush semantics.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_pipeline.py -q -m 'not slow' -p no:cacheprovider
# Durable cycle journal (kubetpu/utils/journal.py): record framing +
# size-cap eviction counting, the chaos journal point's degrade-to-drop
# write contract, the disarmed zero-lock poison test, and the
# armed-vs-disarmed placement parity golden.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_journal.py -q -m 'not slow' -p no:cacheprovider
# Bit-exact replay rig (tools/kubereplay): the journaled-drain replay
# oracle (byte-identical packed placements incl. delta cycles, resyncs
# and a depth-4 pipelined segment), per-record corrupt-skip reasons, and
# the counterfactual contracts (score-weight nonzero / pipelineDepth
# zero divergence).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_replay.py -q -m 'not slow' -p no:cacheprovider
# Exactness prover gate, full half (tools/kubeexact): re-traces every
# exact-marked mesh root, re-proves each cross-shard
# reduction exact (float max/min or int-valued sum < 2**24 via the
# integer-valuedness + interval lattice), re-enumerates the collective
# surface, and fails on any unsuppressed
# exact/* finding, a stale exemption, or DRIFT against the committed
# EXACT_MANIFEST.json in either direction.  Regenerate after an
# intentional change: make exact (python -m tools.kubeexact --write).
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m tools.kubeexact --json
# Exactness prover suite: every prover rule fires on a seeded bad
# snippet (non-integer f32 psum, out-of-range sum, shard_map row-
# gather, raw tie-argmax), clean snippets stay empty,
# manifest regeneration is byte-identical, the drift gate sees both
# directions, and exemption staleness is audited.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_kubeexact.py -q -m 'not slow' -p no:cacheprovider
# Closure prover suite: every close/* rule fires on a seeded bad snippet
# and stays quiet on the good twin, the committed CLOSURE_MANIFEST.json
# regenerates byte-identically, drift is seen in both directions, the
# --check gate runs under a jax import blocker, stale exemptions fire,
# and a churned pipelined drain's dispatched seam signatures are all
# members of the committed closure.
JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" python -m pytest \
	tests/test_kubeclose.py -q -m 'not slow' -p no:cacheprovider
