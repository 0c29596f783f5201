# Developer entrypoints.  `make lint` is the static-analysis gate builders
# run by default; `make test` is the tier-1 suite (which embeds the same
# lint gate via tests/test_kubelint.py).  `make help` lists everything.

.PHONY: help lint lock-graph test sanitize-test race-test flight-test \
	delta-test census census-test aot aot-test chaos-test \
	pipeline-test journal-test replay-test \
	mesh-test exact exact-test close close-test

help:
	@echo "kubetpu targets:"
	@echo "  make lint           kubelint over kubetpu/ (all 7 rule families:"
	@echo "                      host-sync, recompile, numeric, purity,"
	@echo "                      concurrency, delta, exact), JSON CI mode,"
	@echo "                      nonzero on findings"
	@echo "  make lock-graph     print the lock-ownership map + acquisition-"
	@echo "                      order table (README 'Concurrency model')"
	@echo "  make test           tier-1 suite (JAX on CPU, slow tests skipped)"
	@echo "  make sanitize-test  full cycles under KUBETPU_SANITIZE=1"
	@echo "                      (debug_nans, rank-promotion, compile watchdog)"
	@echo "  make race-test      8-thread stress + seeded-violation tests under"
	@echo "                      KUBETPU_RACE=1 (instrumented locks, lock-order"
	@echo "                      + hold-time enforcement, guarded-attr checks)"
	@echo "  make flight-test    flight recorder + decision audit suite (ring"
	@echo "                      wrap/drops, Chrome-trace schema, /debug"
	@echo "                      endpoints, disarmed no-op)"
	@echo "  make delta-test     incremental tensorization suite (delta-vs-"
	@echo "                      rebuild golden equivalence, resync fallbacks,"
	@echo "                      scatter compile-once watchdog)"
	@echo "  make census         regenerate COMPILE_MANIFEST.json from the"
	@echo "                      compile-surface census (tools/kubecensus);"
	@echo "                      run after an INTENTIONAL surface change"
	@echo "  make census-test    census suite: every jaxpr rule fires on a"
	@echo "                      bad snippet, manifest idempotence, drift"
	@echo "                      gate, runtime compile-event matching"
	@echo "  make aot            compile + serialize every COMPILE_MANIFEST"
	@echo "                      variant of the seamed serving programs into"
	@echo "                      artifacts/aot (tools/kubeaot --build) and"
	@echo "                      rewrite the committed AOT_INDEX.json"
	@echo "  make aot-test       AOT suite: serialize/deserialize round trip"
	@echo "                      with bit-identical placements, capture->serve"
	@echo "                      signature hits, env-drift fallback, index"
	@echo "                      gate, persistent-cache config coverage"
	@echo "  make chaos-test     chaos harness + self-healing runtime suite:"
	@echo "                      seeded fault injection (dispatch, delta"
	@echo "                      scatter, aot load, bind/extender/watch"
	@echo "                      transport), deadline demotion, anti-entropy"
	@echo "                      verifier, disarmed-no-op poison test"
	@echo "  make pipeline-test  depth-k pipelined executor suite"
	@echo "                      (kubetpu/pipeline.py): depth-parity"
	@echo "                      placement goldens, gather-window gating on"
	@echo "                      free ring slots, per-slot exemption"
	@echo "                      accounting, chaos-at-depth scatter recovery"
	@echo "  make journal-test   durable cycle journal suite"
	@echo "                      (kubetpu/utils/journal.py): record schema,"
	@echo "                      size-cap eviction counting, chaos write"
	@echo "                      degradation, disarmed zero-lock poison,"
	@echo "                      armed-vs-disarmed placement parity,"
	@echo "                      /debug/journal round trip"
	@echo "  make replay-test    bit-exact replay rig suite (tools/"
	@echo "                      kubereplay): 50+-cycle depth-4 journaled"
	@echo "                      drain replays byte-identical, corrupt-"
	@echo "                      record skip with reason, counterfactual"
	@echo "                      score-weight/pipelineDepth divergence"
	@echo "  make mesh-test      pod-axis mesh scale-out suite (parallel/"
	@echo "                      shardmap.py): (2,4)/(4,2)/(1,8) sharded-vs-"
	@echo "                      unsharded bit-identity through the shard_map"
	@echo "                      auction/scan (tiled + replicated surfaces,"
	@echo "                      windowed rounds, serving path incl. the"
	@echo "                      double-buffered batch upload)"
	@echo "  make exact          re-prove the exact-reduction invariant over"
	@echo "                      every mesh root and rewrite the"
	@echo "                      committed EXACT_MANIFEST.json (tools/"
	@echo "                      kubeexact --write); run after an INTENTIONAL"
	@echo "                      collective surface change"
	@echo "  make exact-test     exactness prover suite: every prover rule"
	@echo "                      fires on a bad snippet, clean snippet empty,"
	@echo "                      manifest byte-idempotence + drift gate,"
	@echo "                      stale-exemption audit, committed manifest"
	@echo "                      passes the pure-JSON --check"
	@echo "  make close          re-prove the compile-surface closure (tools/"
	@echo "                      kubeclose --write): interprocedural provenance"
	@echo "                      of every dispatch-seam static, enumerated"
	@echo "                      reachable signature set, coverage join against"
	@echo "                      the kubecensus registry; rewrites the committed"
	@echo "                      CLOSURE_MANIFEST.json (byte-identical over an"
	@echo "                      unchanged tree); run after an INTENTIONAL seam"
	@echo "                      or config-domain change"
	@echo "  make close-test     closure prover suite: every close/* rule fires"
	@echo "                      on a bad snippet + quiet good twin, manifest"
	@echo "                      byte-idempotence + two-directional drift gate,"
	@echo "                      --check under a jax import blocker, stale-"
	@echo "                      exemption audit, serving-path dispatch-"
	@echo "                      signature membership e2e"

lint:
	./tools/ci_lint.sh

lock-graph:
	python -m tools.kubelint kubetpu/ --lock-graph

test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# full scheduling cycles under the runtime sanitizer (debug_nans,
# rank_promotion=raise, compile-count watchdog)
sanitize-test:
	JAX_PLATFORMS=cpu KUBETPU_SANITIZE=1 python -m pytest \
		tests/test_sanitize.py -q -p no:cacheprovider

# the race harness: stress tests with instrumented locks + guarded-attr
# enforcement (utils/racecheck.py); KUBETPU_RACE=1 arms it process-wide
race-test:
	JAX_PLATFORMS=cpu KUBETPU_RACE=1 python -m pytest \
		tests/test_racecheck.py -q -p no:cacheprovider

# flight recorder + per-pod decision audit (utils/trace.py,
# utils/decisions.py, /debug/flightz + /debug/explain)
flight-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_flightrecorder.py -q -p no:cacheprovider

# incremental tensorization (state/delta.py): golden equivalence vs full
# rebuild, fallback triggers, scatter-program compile-once contract
delta-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_delta.py -q -p no:cacheprovider

# compile-surface census: trace every registered jit root across the
# pow2 ladder and rewrite COMPILE_MANIFEST.json (byte-identical when the
# surface is unchanged); `make lint` / ci_lint.sh fail on drift
census:
	JAX_PLATFORMS=cpu python -m tools.kubecensus --write

census-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_kubecensus.py -q -p no:cacheprovider

# AOT executable artifacts (tools/kubeaot + kubetpu/utils/aot.py):
# deploy-time jit(...).lower().compile() of every manifest variant of the
# seamed serving programs, serialized via jax.experimental
# .serialize_executable; nonzero exit on a capture failure or a
# lowering-sha mismatch vs COMPILE_MANIFEST.json (the bit-identity oracle)
aot:
	JAX_PLATFORMS=cpu python -m tools.kubeaot --build

aot-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_aot.py tests/test_compilation.py -q -p no:cacheprovider

# pod-axis mesh scale-out (kubetpu/parallel/shardmap.py): the explicit
# shard_map auction/scan vs the single-device oracle on the 8-virtual-CPU
# mesh — the previously env-gated (2,4)/(4,2) shapes, ungated
mesh-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_mesh.py -q -m 'not slow' -p no:cacheprovider

# chaos harness (kubetpu/utils/chaos.py): every named injection point's
# seeded recovery-invariant scenario — no lost pods, no double binds,
# mirror/device bit-consistency after induced faults — plus the
# disarmed-hot-path poison test
chaos-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_chaos.py -q -m 'not slow' -p no:cacheprovider

# depth-k pipelined executor (kubetpu/pipeline.py): depth-parity
# placement goldens, the gather-window/free-slot gate, ring exemption
# accounting, ring-slot flight tags, and the chaos-at-depth scatter
# recovery regressions that live next to the delta suite's chain-break
# test
pipeline-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_pipeline.py tests/test_chain.py -q -p no:cacheprovider
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_delta.py -q -k 'depth4 or pipelined' -p no:cacheprovider

# durable cycle journal (kubetpu/utils/journal.py): on-disk record
# store bounds + eviction counting, the chaos journal point's
# degrade-to-drop contract, the disarmed-hot-path poison test, and the
# armed-vs-disarmed placement-parity golden
journal-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_journal.py -q -p no:cacheprovider

# bit-exact replay rig (tools/kubereplay): the journaled-drain replay
# oracle (byte-identical packed placements incl. delta cycles, resyncs
# and a depth-4 pipelined segment), per-record corrupt-skip reasons, and
# the counterfactual divergence contracts (score weight nonzero,
# pipelineDepth zero)
replay-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_replay.py -q -m 'not slow' -p no:cacheprovider

# jaxpr-level exactness prover + collective census (tools/
# kubeexact): abstract interpretation of every exact-marked mesh
# root proves each cross-shard reduction is float max/min or
# an integer-valued sum bounded below 2**24 and enumerates the
# collective surface; --write rewrites the
# committed EXACT_MANIFEST.json (byte-identical when the surface is
# unchanged).  `make lint` / ci_lint.sh fail on drift.
exact:
	JAX_PLATFORMS=cpu python -m tools.kubeexact --write

exact-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_kubeexact.py -q -p no:cacheprovider

# compile-surface closure prover (tools/kubeclose, pure AST — no jax):
# interprocedural provenance of every value reaching a dispatch-seam
# static, enumerated at the committed north-star environment and joined
# against the kubecensus registry's closure_statics; --write rewrites
# the committed CLOSURE_MANIFEST.json (byte-identical when the seam
# surface is unchanged).  `make lint` / ci_lint.sh fail on drift.
close:
	python -m tools.kubeclose --write

close-test:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_kubeclose.py -q -p no:cacheprovider
