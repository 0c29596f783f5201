"""The closure's audited trust base (the twin of kubeexact's
``exact_facts``): finite-domain declarations the AST prover cannot derive
on its own, plus the structured exemptions that carry
reachable-but-deliberately-uncovered signatures.

Everything here is reviewed, committed state: the prover TRUSTS these
tables, so growing one is an explicit diff, and a table row no finding
consumes ages out as ``close/stale-exemption`` (exemptions) or is simply
dead text under review (domains).  No jax imports.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------- domains

# Config classes whose instances are per-deployment constants: a value of
# one of these types is label config-constant (finite: profiles are
# loaded once at scheduler construction and never mutated mid-serve; the
# ProgramConfig NamedTuple is hashable and IS the jit static key).
CONFIG_CLASSES = ("ProgramConfig", "KubeSchedulerConfiguration",
                  "KubeSchedulerProfile")

# Audited value domains of the config FIELDS that reach dispatch seams in
# static positions.  A field read without a row here stays a symbolic
# config-constant (finite per deployment, not enumerated), so every
# multi-valued axis the closure crosses exists because a row here
# declared it — declaring the domain is the reviewed act that makes the
# enumeration sound.  Value: a tuple of canonical reprs
# (registry-enumerated), or None to pin the field symbolic explicitly.
CONFIG_FIELD_DOMAINS: Dict[Tuple[str, str], Optional[Tuple[str, ...]]] = {
    ("KubeSchedulerConfiguration", "mode"): ("'gang'", "'sequential'"),
    # read on the seam path only to normalize the static out of the
    # program key (gang) or via the _seq_cfg replica (sequential)
    ("ProgramConfig", "percentage_of_nodes_to_score"): None,
}

# Host-state dict keys that hold pow2-bucketed CAPACITIES by construction
# (state/tensors.py: every ``*_cap`` slot is written from pow2_bucket of
# a vocab/world size).  A Subscript read of one of these keys is label
# pow2-bucketed; anything else stays unbounded.
STATE_CAPACITY_KEYS = ("_kv_cap",)

# Helper callables (resolved dotted suffix) whose RESULT class the prover
# pins without reading the body: register_mesh tokens are one per mesh
# shape (bounded by the deployment's mesh profiles).
MESH_KEY_FUNCS = ("register_mesh",)

# --------------------------------------------------------- extra roots

# Seamed serving programs dispatched as a Python-level jit-object PAIR
# instead of through aot.dispatch: the host entry picks one of two jit
# twins on a boolean.  The closure enumerates them from the host entry's
# parameter provenance; ``axes`` maps the closure axis name to the host
# parameter carrying it.
EXTRA_ROOTS = (
    {
        "program": "_apply_cluster_delta",
        "entry": "kubetpu.models.programs:apply_cluster_delta",
        "axes": {"donate": "donate"},
    },
    {
        "program": "_apply_terms_delta",
        "entry": "kubetpu.models.programs:apply_terms_delta",
        "axes": {"donate": "donate"},
    },
    {
        "program": "_apply_delta_body",
        "entry": "kubetpu.parallel.shardmap:apply_cluster_delta_mesh",
        "axes": {"donate": "donate"},
        # the shard_map twins additionally key on the mesh token
        "symbolic": {"mesh_key": "mesh-key"},
    },
)

# ------------------------------------------------------------ exemptions

# Structured (rule, key, reason) exemptions.  ``key`` is the finding's
# stable key (program + sorted axis assignment for uncaptured-signature;
# program:tag for unreachable-manifest-row).  Every exemption must name
# the FALLBACK PATH that serves the exempted signature; one that matches
# no finding is itself a close/stale-exemption finding.
EXEMPTIONS: Tuple[Tuple[str, str, str], ...] = (
    # ---- branch correlations the flow-insensitive join cannot see ----
    # _shardmap_gang: gang_surface returns "replicated" whenever
    # intra_batch_topology=True, so the topology x tiled cross is
    # unreachable (parallel/shardmap.py gang_surface).
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=True "
     "score_bias=absent surface='tiled'",
     "statically excluded before the seam: gang_surface routes every "
     "intra_batch_topology=True dispatch to surface='replicated'"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=True "
     "score_bias=absent surface='tiled'",
     "statically excluded before the seam: gang_surface routes every "
     "intra_batch_topology=True dispatch to surface='replicated'"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=True "
     "score_bias=present surface='tiled'",
     "statically excluded before the seam: gang_surface routes every "
     "intra_batch_topology=True dispatch to surface='replicated'"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=True "
     "score_bias=present surface='tiled'",
     "statically excluded before the seam: gang_surface routes every "
     "intra_batch_topology=True dispatch to surface='replicated'"),
    # ---- host-score-bias crosses: served by the traced fallback ----
    # The bias-variant census row covers the common host-score profile
    # (host_ok AND score_bias from the same framework runner).  The rarer
    # crosses (a Score plugin without a Filter plugin, bias on the
    # term-free route) fall back at the seam to the traced
    # jit dispatch: ONE bounded compile per (program, bucket), warmed by
    # Scheduler.prewarm's score_bias=warm_bias pass when the profile
    # declares host score plugins, and fenced by the per-(program,
    # shape) recompile watchdog.
    ("close/uncaptured-signature",
     "_schedule_gang host_ok=absent intra_batch_topology=True "
     "score_bias=present",
     "score-plugin-without-filter-plugin profile: traced-jit fallback at "
     "the seam, prewarmed by the score_bias=warm_bias prewarm variant"),
    ("close/uncaptured-signature",
     "_schedule_gang host_ok=absent intra_batch_topology=False "
     "score_bias=present",
     "score-plugin-without-filter-plugin profile on a term-free batch: "
     "traced-jit fallback at the seam, prewarmed by the "
     "score_bias=warm_bias prewarm variant"),
    ("close/uncaptured-signature",
     "_schedule_gang host_ok=present intra_batch_topology=False "
     "score_bias=present",
     "host filter+score profile on a term-free batch: traced-jit "
     "fallback at the seam, prewarmed by the score_bias=warm_bias "
     "prewarm variant"),
    ("close/uncaptured-signature",
     "_schedule_sequential host_ok=absent score_bias=present",
     "score-plugin-without-filter-plugin profile: traced-jit fallback at "
     "the seam, prewarmed by the score_bias=warm_bias prewarm variant"),
    ("close/uncaptured-signature",
     "_schedule_sequential host_ok=present score_bias=present",
     "host filter+score profile: traced-jit fallback at the seam, "
     "prewarmed by the score_bias=warm_bias prewarm variant"),
    # ---- mesh twins: the kubeaot HONEST COVERAGE NOTE ----
    # Census rows for the shard_map family capture at the (1, 1)-mesh
    # rung and the mesh key is part of the signature, so a fleet mesh's
    # dispatches fall back per key to the trace path regardless — the
    # rows pin the build-time sha oracle, not a production warm start
    # (tools/kubeaot/build.py AOT_PROGRAMS note; deploy-shaped mesh
    # capture is the ROADMAP item 1 residual).  The host_ok/score_bias
    # crosses and the degraded-surface route ride that same fallback.
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=False "
     "score_bias=absent surface='replicated'",
     "term-free batch degraded to the replicated surface (unsupported "
     "score plugin / soft-spread / non-dividing axis): traced-jit "
     "fallback per mesh key — the kubeaot honest-coverage note's "
     "fallback path"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=True "
     "score_bias=absent surface='replicated'",
     "mesh profile with host filter plugins: traced-jit fallback per "
     "mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=False "
     "score_bias=absent surface='replicated'",
     "mesh host-filter cross on the degraded surface: traced-jit "
     "fallback per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=False "
     "score_bias=absent surface='tiled'",
     "mesh host-filter cross on the tiled surface: traced-jit fallback "
     "per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=True "
     "score_bias=present surface='replicated'",
     "mesh host-score cross: traced-jit fallback per mesh key (kubeaot "
     "honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=True "
     "score_bias=present surface='replicated'",
     "mesh host filter+score cross: traced-jit fallback per mesh key "
     "(kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=False "
     "score_bias=present surface='replicated'",
     "mesh host-score cross on the degraded surface: traced-jit "
     "fallback per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=False "
     "score_bias=present surface='replicated'",
     "mesh host filter+score cross on the degraded surface: traced-jit "
     "fallback per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=absent intra_batch_topology=False "
     "score_bias=present surface='tiled'",
     "mesh host-score cross on the tiled surface: traced-jit fallback "
     "per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_gang host_ok=present intra_batch_topology=False "
     "score_bias=present surface='tiled'",
     "mesh host filter+score cross on the tiled surface: traced-jit "
     "fallback per mesh key (kubeaot honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_sequential host_ok=present score_bias=absent",
     "mesh host-filter cross: traced-jit fallback per mesh key (kubeaot "
     "honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_sequential host_ok=absent score_bias=present",
     "mesh host-score cross: traced-jit fallback per mesh key (kubeaot "
     "honest-coverage note)"),
    ("close/uncaptured-signature",
     "_shardmap_sequential host_ok=present score_bias=present",
     "mesh host filter+score cross: traced-jit fallback per mesh key "
     "(kubeaot honest-coverage note)"),
)
