"""The explicit jit-root registry: every device program the scheduler can
dispatch, with a builder that reproduces its REAL input structures at each
rung of the pow2 bucket ladder.

The worlds are built through the same tensorization path serving uses
(hollow nodes/pods -> NodeInfo -> SnapshotBuilder -> PodBatchBuilder ->
ProgramConfig), so the abstract avals the census traces are byte-for-byte
the avals a serving cycle of that shape would compile — not a hand-kept
approximation that silently drifts from the builders.  Worlds are
deterministic (seeded generators, insertion-ordered vocabs), which is what
makes the committed manifest idempotent.

Every entry carries the qualname kubelint's call graph reports for the
root, so the census can prove the registry covers the whole discovered
compile surface (census/unregistered-root).  Rule exemptions require an
audited reason, mirroring the kubelint suppression convention.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple


class Rung(NamedTuple):
    """One ladder rung: the logical world size a variant is traced at.
    Axis CAPACITIES are derived by the real builders (pow2_bucket), so a
    rung names a workload shape, not raw tensor dims."""
    name: str
    n_nodes: int
    n_pods: int


# The committed ladder: the small rung pins the minimum-bucket programs
# (every axis at its pow2 floor); the mid rung exercises genuinely distinct
# buckets on every axis (nodes, batch, labels, terms, selectors).  Tracing
# cost is shape-independent, but each rung is a manifest row per program —
# keep the ladder intentional, not exhaustive.
DEFAULT_LADDER: Tuple[Rung, ...] = (
    Rung("n8_b8", 8, 8),
    Rung("n64_b64", 64, 64),
)


class CensusWorld:
    """One deterministic world at a rung, tensorized for tracing."""

    def __init__(self, rung: Rung):
        import jax
        import numpy as np

        from kubetpu.api import types as api
        from kubetpu.framework.types import NodeInfo, PodInfo
        from kubetpu.harness import hollow
        from kubetpu.models import programs
        from kubetpu.models.batch import PodBatchBuilder
        from kubetpu.scheduler import Scheduler
        from kubetpu.state.tensors import SnapshotBuilder

        self.rung = rung
        nodes = hollow.make_nodes(rung.n_nodes, zones=4)
        # existing pods: one per node with app-group labels, every fourth
        # carrying hostname anti-affinity so the cluster-side term axes
        # (filter_terms/score_terms) are non-degenerate like real worlds
        existing = hollow.make_pods(rung.n_nodes, prefix="ex-",
                                    group_labels=8)
        for i, p in enumerate(existing):
            if i % 4 == 0:
                hollow.with_anti_affinity(p, api.LABEL_HOSTNAME)
        infos = []
        for i, n in enumerate(nodes):
            ni = NodeInfo(n)
            p = existing[i]
            p.spec.node_name = n.name
            ni.add_pod(p)
            infos.append(ni)
        pending = hollow.make_pods(rung.n_pods, prefix="pend-",
                                   group_labels=8)
        for i, p in enumerate(pending):
            # the blended topology mix: 1/3 soft zone spread, 1/5
            # hostname anti-affinity, 1/7 zone affinity
            if i % 3 == 0:
                hollow.with_spread(p, api.LABEL_ZONE, when="ScheduleAnyway")
            if i % 5 == 0:
                hollow.with_anti_affinity(p, api.LABEL_HOSTNAME)
            if i % 7 == 1:
                hollow.with_affinity(p, api.LABEL_ZONE)
        self.node_infos = infos
        self.pinfos = [PodInfo(p) for p in pending]
        sb = SnapshotBuilder()
        sb.intern_pending(self.pinfos)
        self.builder = sb
        self.host = sb.build(infos)
        self.cluster = self.host.to_device()
        pb = PodBatchBuilder(sb.table)
        self.batch = jax.tree.map(np.asarray, pb.build(self.pinfos))
        self.table = sb.table
        self.cfg = programs.ProgramConfig(
            filters=programs.DEFAULT_FILTER_PLUGINS,
            scores=programs.DEFAULT_SCORE_PLUGINS,
            hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME),
                                 0),
            # the serving loop restricts the same-pair matmuls to the
            # batch's term keys; reproduce that static exactly
            active_topo_keys=Scheduler._batch_topo_keys(sb.table,
                                                        self.pinfos))
        self.rng = jax.random.PRNGKey(0)
        self.B = int(self.batch.valid.shape[0])
        self.N = int(self.cluster.allocatable.shape[0])
        self.P = int(self.cluster.pod_valid.shape[0])
        self.R = int(self.cluster.allocatable.shape[1])

    # shared derived inputs ------------------------------------------------

    def host_ok(self):
        import numpy as np
        return np.ones((self.B, self.N), bool)

    def score_bias(self):
        import numpy as np
        return np.zeros((self.B, self.N), np.float32)

    def nominated(self):
        """(nom overlay, nom PodBatch, rows, prio) mirroring the
        scheduler's addNominatedPods two-pass overlay build."""
        import jax
        import numpy as np

        from kubetpu.models.batch import PodBatchBuilder, build_nominated

        entries = [(self.pinfos[0], 0, 0), (self.pinfos[1], 1, -1)]
        nom = build_nominated(entries, self.table)
        pb = PodBatchBuilder(self.table)
        nom_pb = jax.tree.map(np.asarray,
                              pb.build([e[0] for e in entries]))
        # rows/prio are sized to the PADDED nominated bucket, exactly like
        # Scheduler._nominated_overlay_mask
        M = int(np.asarray(nom_pb.valid).shape[0])
        rows = np.full((M,), -1, np.int32)
        prio = np.zeros((M,), np.int32)
        for i, e in enumerate(entries):
            rows[i] = e[1]
            prio[i] = e[0].pod.priority()
        return nom, nom_pb, rows, prio


_WORLDS: Dict[Rung, CensusWorld] = {}


def build_world(rung: Rung) -> CensusWorld:
    w = _WORLDS.get(rung)
    if w is None:
        w = _WORLDS[rung] = CensusWorld(rung)
    return w


@dataclasses.dataclass(frozen=True)
class Entry:
    """One registered jit root.

    ``build(world)`` returns ``(fn, args, kwargs)`` — the jit object plus
    the concrete call the serving path makes.  kwargs may mix static
    values (hashable non-arrays, consumed by static_argnames) and optional
    dynamic arrays (e.g. host_ok); the tracer tells them apart by type.
    ``tag`` distinguishes registry variants that compile under the same
    program name (e.g. donated vs shared scatter).  ``exempt`` maps census
    rule ids to audited reasons (the kubelint suppression convention:
    reasonless exemptions are themselves findings)."""
    program: str
    qualname: str
    build: Callable[[CensusWorld], tuple]
    tag: str = ""
    meshable: bool = False
    # the builder's inputs are ALREADY committed to a mesh and the
    # lowering must keep their NamedShardings (the shard_map family:
    # serving dispatches these with committed-sharded residents, and the
    # AOT capture's sha must equal the manifest's) — the per-entry twin
    # of the meshable variants' keep_sharding flow
    keep_sharding: bool = False
    donate_argnums: Tuple[int, ...] = ()
    # kwarg names / positional indices the jit treats as STATIC (mirrors
    # the decorator's static_argnames); every other arg is a traced input
    # — including Python scalars, which jit sees as weak rank-0 avals.
    # Builders mirror the SERVING call form (positional vs keyword), so
    # the manifest's flattened aval order equals the compile log's.
    static_argnames: Tuple[str, ...] = ()
    static_argnums: Tuple[int, ...] = ()
    ladder: Tuple[Rung, ...] = DEFAULT_LADDER
    exempt: Tuple[Tuple[str, str], ...] = ()
    # ---- exactness prover metadata (tools/kubeexact) -------------------
    # exact=True opts the entry into the jaxpr-level exact-reduction
    # proof: every cross-shard/cross-tile float reduction must be proved
    # max/min or an integer-valued sum bounded below 2**24 at the
    # north-star shapes.  The shard_map family (the roots with
    # collectives) must all be exact=True.
    exact: bool = False
    # (input-path substring, fact name): seeds the abstract interpreter
    # with invariants the builders guarantee but tracing cannot see —
    # e.g. cluster.zone_hot rows are one-hot ("onehot_rows").  Facts are
    # part of the audited trust base and are committed in the manifest.
    exact_facts: Tuple[Tuple[str, str], ...] = ()
    # (rule, reason) exemptions for exactness findings, mirroring
    # ``exempt``: reasonless or stale entries are themselves findings.
    exact_exempt: Tuple[Tuple[str, str], ...] = ()
    # ---- closure prover metadata (tools/kubeclose) ---------------------
    # The (axis, value) assignment this entry covers in the program's
    # enumerated reachable-signature set: one pair per MULTI-VALUED
    # closure axis (enumerated statics as canonical reprs — "'tiled'",
    # "True" — and optional-dynamic presence axes as "absent"/"present").
    # kubeclose joins CLOSURE_MANIFEST combos against these, so a combo
    # no entry matches is close/uncaptured-signature and an entry whose
    # assignment matches no reachable combo is close/unreachable-
    # manifest-row.  Single-valued and symbolic axes (cfg, mesh_key, the
    # pad ladders) are carried by the manifest itself, not repeated here.
    closure_statics: Tuple[Tuple[str, str], ...] = ()

    @property
    def key(self) -> str:
        return self.program + (":" + self.tag if self.tag else "")


def _filter_and_score(w):
    from kubetpu.models import programs
    return programs.filter_and_score, (w.cluster, w.batch, w.cfg), {}


def _filter_and_score_hostok(w):
    from kubetpu.models import programs
    return (programs.filter_and_score, (w.cluster, w.batch, w.cfg),
            {"host_ok": w.host_ok()})


def _schedule_batch(w):
    from kubetpu.models import programs
    return (programs.schedule_batch, (w.cluster, w.batch, w.cfg, w.rng),
            {})


def _explain_filters(w):
    from kubetpu.models import programs
    return programs.explain_filters, (w.cluster, w.batch, w.cfg), {}


def _explain_verdicts(w):
    from kubetpu.models import programs
    return programs._explain_verdicts, (w.cluster, w.batch, w.cfg), {}


def _explain_verdicts_hostok(w):
    from kubetpu.models import programs
    # host_ok as KEYWORD, the serving seam's call form (scheduler prewarm
    # and the audit path pass host_ok=...) — jit binds either spelling to
    # the same avals, but the AOT signature keys on the call treedef, so
    # a positional capture could never be hit by serving dispatch
    return (programs._explain_verdicts,
            (w.cluster, w.batch, w.cfg), {"host_ok": w.host_ok()})


def _filter_verdicts(w):
    from kubetpu.models import programs
    return programs.filter_verdicts, (w.cluster, w.batch, w.cfg), {}


def _wave_cfg(cfg):
    return cfg._replace(filters=tuple(
        f for f in cfg.filters
        if f not in ("PodTopologySpread", "InterPodAffinity")))


def _whatif_static_ok(w):
    from kubetpu.models import programs
    return (programs.whatif_static_ok,
            (w.cluster, w.batch, _wave_cfg(w.cfg)), {})


def _whatif_wave(w):
    import numpy as np

    from kubetpu.models import programs
    B, C, K, S, R = 8, 8, 8, 8, w.R
    static_ok = np.ones((B, w.N), bool)
    return (programs.whatif_wave,
            (w.cluster, static_ok,
             np.zeros((B, R), np.float32),          # wave_req
             np.zeros((B, C), np.int32),            # cand_rows
             np.zeros((B, C), bool),                # cand_valid
             np.zeros((B, C, R), np.float32),       # nom_add
             np.zeros((S, K, R), np.float32),       # tab_req
             np.zeros((S, K), bool),                # tab_valid
             np.zeros((B, C), np.int32)),           # cand_idx
            {})


def _whatif_reprieve(w):
    import numpy as np

    from kubetpu import preemption
    from kubetpu.models.batch import PodBatchBuilder
    import jax
    C, K, R, P = 8, 8, w.R, w.P
    pb = PodBatchBuilder(w.table)
    batch1 = jax.tree.map(np.asarray, pb.build(w.pinfos[:1]))
    return (preemption._whatif_reprieve,
            (w.cluster, batch1, _wave_cfg(w.cfg),
             np.zeros((C,), np.int32),            # cand_rows
             np.ones((C, P), bool),               # rm_valid
             np.zeros((C, R), np.float32),        # rm_req
             np.zeros((C, 2), np.float32),        # rm_nz
             np.full((C, K), -1, np.int32),       # vic_row
             np.zeros((C, K, R), np.float32),     # vic_req
             np.zeros((C, K, 2), np.float32)),    # vic_nz
            {})


def _nominated_fit_mask(w):
    from kubetpu.models import programs
    nom, _, _, _ = w.nominated()
    return programs.nominated_fit_mask, (w.cluster, w.batch, nom), {}


def _nominated_topology_mask(w):
    from kubetpu.models import programs
    _, nom_pb, rows, prio = w.nominated()
    cfg = w.cfg._replace(scores=())
    return (programs.nominated_topology_mask,
            (w.cluster, nom_pb, rows, prio, w.batch, cfg), {})


def _schedule_gang(w):
    from kubetpu.models import gang
    return (gang._schedule_gang, (w.cluster, w.batch, w.cfg, w.rng), {})


def _schedule_gang_hostok(w):
    from kubetpu.models import gang
    return (gang._schedule_gang, (w.cluster, w.batch, w.cfg, w.rng),
            {"host_ok": w.host_ok()})


def _schedule_gang_bias(w):
    from kubetpu.models import gang
    return (gang._schedule_gang, (w.cluster, w.batch, w.cfg, w.rng),
            {"host_ok": w.host_ok(), "score_bias": w.score_bias()})


def _schedule_gang_notopo(w):
    from kubetpu.models import gang
    # the term-free serving form: a batch with no topology terms routes
    # intra_batch_topology=False (scheduler's needs_topo gate) — a
    # DISTINCT static combination from the plain entry (intra=True) that
    # the closure prover found reachable-but-uncovered: the first
    # term-free cycle of a default-config deployment compiled cold on
    # the serving path
    return (gang._schedule_gang, (w.cluster, w.batch, w.cfg, w.rng),
            {"intra_batch_topology": False})


def _schedule_gang_notopo_hostok(w):
    from kubetpu.models import gang
    # host-filter cycles over a term-free batch
    return (gang._schedule_gang, (w.cluster, w.batch, w.cfg, w.rng),
            {"host_ok": w.host_ok(), "intra_batch_topology": False})


def _shardmap_mesh(w):
    """A (1, 1) mesh + registered key: the shard_map twins trace on a
    single-device mesh exactly like the meshable @mesh variants — the
    census environment has one CPU device, and the program STRUCTURE
    (explicit collectives, replicated vs tiled surface) is what the
    manifest rows pin, not the device count."""
    from kubetpu.parallel import mesh as pmesh
    from kubetpu.parallel import shardmap
    m = pmesh.make_mesh((1, 1))
    return m, shardmap.register_mesh(m)


def _shardmap_place(w, m):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from kubetpu.parallel import mesh as pmesh
    cluster = pmesh.shard_cluster(w.cluster, m)
    batch = pmesh.shard_batch(w.batch, m)
    rng = pmesh._put(w.rng, NamedSharding(m, PartitionSpec()))
    return cluster, batch, rng


def _shardmap_gang_replicated(w):
    from kubetpu.parallel import shardmap
    m, key = _shardmap_mesh(w)
    cluster, batch, rng = _shardmap_place(w, m)
    # the serving call form for topology batches (scheduler needs_topo
    # routes intra_batch_topology=True -> surface "replicated")
    return (shardmap._shardmap_gang, (cluster, batch, w.cfg, rng),
            {"mesh_key": key, "intra_batch_topology": True,
             "residual_window": 512, "surface": "replicated"})


def _shardmap_gang_tiled(w):
    from kubetpu.parallel import shardmap
    m, key = _shardmap_mesh(w)
    cluster, batch, rng = _shardmap_place(w, m)
    # the term-free scale surface: gather-free tiled auction
    return (shardmap._shardmap_gang, (cluster, batch, w.cfg, rng),
            {"mesh_key": key, "intra_batch_topology": False,
             "residual_window": 512, "surface": "tiled"})


def _shardmap_sequential(w):
    from kubetpu.parallel import shardmap
    m, key = _shardmap_mesh(w)
    cluster, batch, rng = _shardmap_place(w, m)
    return (shardmap._shardmap_sequential,
            (cluster, batch, _seq_cfg(w), rng),
            {"mesh_key": key, "hard_pod_affinity_weight": 1.0,
             "start_index": 0})


def _shardmap_delta(w, donate):
    import jax
    from kubetpu.parallel import mesh as pmesh
    from kubetpu.parallel import shardmap
    m, key = _shardmap_mesh(w)
    cluster = pmesh.shard_cluster(w.cluster, m)
    delta = pmesh.replicate(
        jax.tree.map(jax.numpy.asarray, _cluster_delta(w)), m)
    fn = (shardmap._shardmap_apply_delta_donated if donate
          else shardmap._shardmap_apply_delta_shared)
    return fn, (cluster, delta), {"mesh_key": key}


def _shardmap_delta_donated(w):
    return _shardmap_delta(w, True)


def _shardmap_delta_shared(w):
    return _shardmap_delta(w, False)


def _seq_cfg(w):
    # the serving loop passes 0 (= the reference's ADAPTIVE default,
    # types.go:251) unless a profile pins a percentage; the adaptive
    # branch reads start_index, so the static changes the pruned arg set
    return w.cfg._replace(percentage_of_nodes_to_score=0)


def _schedule_sequential(w):
    from kubetpu.models import sequential
    return (sequential._schedule_sequential,
            (w.cluster, w.batch, _seq_cfg(w), w.rng),
            {"hard_pod_affinity_weight": 1.0, "start_index": 0})


def _schedule_sequential_hostok(w):
    from kubetpu.models import sequential
    return (sequential._schedule_sequential,
            (w.cluster, w.batch, _seq_cfg(w), w.rng),
            {"hard_pod_affinity_weight": 1.0, "start_index": 0,
             "host_ok": w.host_ok()})


def _materialize_assigned(w):
    import numpy as np

    from kubetpu.models import gang
    from kubetpu.utils.intern import pow2_bucket
    ta = int(w.batch.raa.valid.shape[1])
    p_next = pow2_bucket(w.P + w.B)
    e_next = pow2_bucket(int(w.cluster.filter_terms.valid.shape[0])
                         + w.B * ta)
    Np = int(w.cluster.ports.shape[1])
    return (gang._materialize_assigned,
            (w.cluster, w.batch,
             np.zeros((w.B,), np.int32),                 # chosen
             np.asarray(w.cluster.requested),            # requested
             np.asarray(w.cluster.nonzero_requested),    # nz
             np.zeros((w.N, Np), np.float32)),           # ports_used
            {"pad_pods_to": p_next, "pad_terms_to": e_next,
             "extend_score_terms": True,
             "hard_pod_affinity_weight": 1.0})


def _cluster_delta(w):
    from kubetpu.state.tensors import gather_delta
    return gather_delta(w.host, [0], [0])


def _apply_delta_donated(w):
    import jax

    from kubetpu.models import programs
    delta = jax.tree.map(jax.numpy.asarray, _cluster_delta(w))
    return (programs._apply_cluster_delta_donated, (w.cluster, delta), {})


def _apply_delta_shared(w):
    import jax

    from kubetpu.models import programs
    delta = jax.tree.map(jax.numpy.asarray, _cluster_delta(w))
    return (programs._apply_cluster_delta_shared, (w.cluster, delta), {})


def _terms_delta_args(w):
    import jax

    from kubetpu.state.tensors import gather_terms_delta, term_slots
    a = w.host.arrays
    tables = (a["filter_terms"], a["score_terms"])
    deltas = tuple(gather_terms_delta(t, [0], 8) for t in tables)
    return jax.tree.map(jax.numpy.asarray,
                        tuple(map(term_slots, tables)) + deltas)


def _apply_terms_donated(w):
    from kubetpu.models import programs
    return (programs._apply_terms_delta_donated, _terms_delta_args(w), {})


def _apply_terms_shared(w):
    from kubetpu.models import programs
    return (programs._apply_terms_delta_shared, _terms_delta_args(w), {})


def _densify_kv(w):
    import jax.numpy as jnp

    from kubetpu.state.tensors import _densify_ids
    a = w.host.arrays
    return (_densify_ids, (jnp.asarray(a["_kv_ids"]),),
            {"L": a["_kv_cap"]})


def _densify_pod_kv(w):
    import jax.numpy as jnp

    from kubetpu.state.tensors import _densify_ids
    a = w.host.arrays
    return (_densify_ids, (jnp.asarray(a["_pod_kv_ids"]),),
            {"L": a["_kv_cap"]})


def _volume_mask(w):
    """The device volume-family mask, built from a PVC-carrying twin of
    the rung world."""
    import jax
    import random

    from kubetpu.api import types as api
    from kubetpu.client.store import ClusterStore
    from kubetpu.state import volumes as svol

    rng = random.Random(0)
    zones = [f"zone-{i}" for i in range(4)]
    store = ClusterStore()
    pods = [pi.pod for pi in w.pinfos]
    for i, p in enumerate(pods):
        zone = rng.choice(zones)
        store.add(api.PersistentVolume(
            metadata=api.ObjectMeta(name=f"census-pv-{i}",
                                    labels={api.LABEL_ZONE: zone})))
        store.add(api.PersistentVolumeClaim(
            metadata=api.ObjectMeta(name=f"census-claim-{i}",
                                    namespace=p.namespace),
            volume_name=f"census-pv-{i}"))
        p.spec.volumes = [
            api.Volume(name="data",
                       persistent_volume_claim=f"census-claim-{i}"),
            api.Volume(name="scratch",
                       aws_elastic_block_store=f"ebs-{i % 4}"),
        ]
    overlay = svol.build_volume_overlay(
        store, w.node_infos, pods, w.table, svol.DEVICE_COVERED_PLUGINS)
    assert overlay is not None
    overlay = jax.tree.map(jax.numpy.asarray, overlay)
    for p in pods:
        p.spec.volumes = []          # leave the shared world untouched
    return (svol._volume_mask,
            (w.cluster.kv, w.cluster.keymask, w.cluster.num, overlay), {})


ENTRIES: List[Entry] = [
    Entry("filter_and_score", "kubetpu.models.programs:filter_and_score",
          _filter_and_score, meshable=True, static_argnums=(2,)),
    Entry("filter_and_score", "kubetpu.models.programs:filter_and_score",
          _filter_and_score_hostok, tag="hostok", static_argnums=(2,)),
    Entry("schedule_batch", "kubetpu.models.programs:schedule_batch",
          _schedule_batch, meshable=True, static_argnums=(2,)),
    Entry("explain_filters", "kubetpu.models.programs:explain_filters",
          _explain_filters, static_argnums=(2,)),
    Entry("_explain_verdicts", "kubetpu.models.programs:_explain_verdicts",
          _explain_verdicts, static_argnums=(2,),
          closure_statics=(("host_ok", "absent"),)),
    Entry("_explain_verdicts", "kubetpu.models.programs:_explain_verdicts",
          _explain_verdicts_hostok, tag="hostok", static_argnums=(2,),
          closure_statics=(("host_ok", "present"),)),
    Entry("filter_verdicts", "kubetpu.models.programs:filter_verdicts",
          _filter_verdicts, static_argnums=(2,)),
    Entry("whatif_static_ok", "kubetpu.models.programs:whatif_static_ok",
          _whatif_static_ok, static_argnums=(2,)),
    Entry("whatif_wave", "kubetpu.models.programs:whatif_wave",
          _whatif_wave, static_argnames=()),
    Entry("_whatif_reprieve", "kubetpu.preemption:_whatif_reprieve",
          _whatif_reprieve, static_argnums=(2,)),
    Entry("nominated_fit_mask",
          "kubetpu.models.programs:nominated_fit_mask",
          _nominated_fit_mask, static_argnames=()),
    Entry("nominated_topology_mask",
          "kubetpu.models.programs:nominated_topology_mask",
          _nominated_topology_mask, static_argnums=(5,)),
    Entry("_schedule_gang", "kubetpu.models.gang:_schedule_gang",
          _schedule_gang, meshable=True, static_argnums=(2,),
          closure_statics=(("host_ok", "absent"),
                           ("intra_batch_topology", "True"),
                           ("score_bias", "absent"))),
    Entry("_schedule_gang", "kubetpu.models.gang:_schedule_gang",
          _schedule_gang_hostok, tag="hostok", static_argnums=(2,),
          closure_statics=(("host_ok", "present"),
                           ("intra_batch_topology", "True"),
                           ("score_bias", "absent"))),
    Entry("_schedule_gang", "kubetpu.models.gang:_schedule_gang",
          _schedule_gang_bias, tag="bias", static_argnums=(2,),
          closure_statics=(("host_ok", "present"),
                           ("intra_batch_topology", "True"),
                           ("score_bias", "present"))),
    Entry("_schedule_gang", "kubetpu.models.gang:_schedule_gang",
          _schedule_gang_notopo, tag="notopo", static_argnums=(2,),
          static_argnames=("intra_batch_topology",),
          closure_statics=(("host_ok", "absent"),
                           ("intra_batch_topology", "False"),
                           ("score_bias", "absent"))),
    Entry("_schedule_gang", "kubetpu.models.gang:_schedule_gang",
          _schedule_gang_notopo_hostok, tag="notopo_hostok",
          static_argnums=(2,),
          static_argnames=("intra_batch_topology",),
          closure_statics=(("host_ok", "present"),
                           ("intra_batch_topology", "False"),
                           ("score_bias", "absent"))),
    Entry("_schedule_sequential",
          "kubetpu.models.sequential:_schedule_sequential",
          _schedule_sequential, meshable=True, static_argnums=(2,),
          closure_statics=(("host_ok", "absent"),
                           ("score_bias", "absent"))),
    Entry("_schedule_sequential",
          "kubetpu.models.sequential:_schedule_sequential",
          _schedule_sequential_hostok, tag="hostok", static_argnums=(2,),
          closure_statics=(("host_ok", "present"),
                           ("score_bias", "absent"))),
    Entry("_materialize_assigned",
          "kubetpu.models.gang:_materialize_assigned",
          _materialize_assigned,
          static_argnames=("pad_pods_to", "pad_terms_to",
                           "extend_score_terms")),
    Entry("_apply_cluster_delta",
          "kubetpu.models.programs:_apply_cluster_delta",
          _apply_delta_donated, tag="donated", donate_argnums=(0,),
          static_argnames=(),
          closure_statics=(("donate", "True"),),
          exempt=(("census/donation-unconsumed",
                   "by design: the four vocab-side tables (image_size/"
                   "image_spread/taint_is_hard/taint_is_prefer) are "
                   "REPLACED wholesale from the delta args, so their "
                   "donated twins have no output to alias into — tiny "
                   "[I]/[T] buffers, the [N,.]/[P,.] residents all "
                   "alias (50/54)"),)),
    Entry("_apply_cluster_delta",
          "kubetpu.models.programs:_apply_cluster_delta",
          _apply_delta_shared, tag="shared", static_argnames=(),
          closure_statics=(("donate", "False"),)),
    Entry("_apply_terms_delta",
          "kubetpu.models.programs:_apply_terms_delta",
          _apply_terms_donated, tag="donated", donate_argnums=(0, 1),
          static_argnames=(),
          closure_statics=(("donate", "True"),)),
    Entry("_apply_terms_delta",
          "kubetpu.models.programs:_apply_terms_delta",
          _apply_terms_shared, tag="shared", static_argnames=(),
          closure_statics=(("donate", "False"),)),
    Entry("_densify_ids", "kubetpu.state.tensors:_densify_ids",
          _densify_kv, tag="kv", static_argnames=("L",)),
    Entry("_densify_ids", "kubetpu.state.tensors:_densify_ids",
          _densify_pod_kv, tag="pod_kv", static_argnames=("L",)),
    Entry("_volume_mask", "kubetpu.state.volumes:_volume_mask",
          _volume_mask, static_argnames=()),
    # ---- pod-axis mesh scale-out (parallel/shardmap.py): the explicit
    # shard_map programs the mesh serving path dispatches — the legacy
    # gspmd twins above (meshable @mesh variants) cover the OLD lowering
    Entry("_shardmap_gang", "kubetpu.parallel.shardmap:_shardmap_gang",
          _shardmap_gang_replicated, tag="replicated",
          keep_sharding=True, static_argnums=(2,),
          static_argnames=("mesh_key", "intra_batch_topology",
                           "residual_window", "surface"),
          exact=True,
          closure_statics=(("host_ok", "absent"),
                           ("intra_batch_topology", "True"),
                           ("score_bias", "absent"),
                           ("surface", "'replicated'"))),
    Entry("_shardmap_gang", "kubetpu.parallel.shardmap:_shardmap_gang",
          _shardmap_gang_tiled, tag="tiled", keep_sharding=True,
          static_argnums=(2,),
          static_argnames=("mesh_key", "intra_batch_topology",
                           "residual_window", "surface"),
          exact=True,
          # SnapshotBuilder writes zone_hot as a one-hot zone-membership
          # row per node (state/tensors.py); the zone-count psum's 2**24
          # proof rests on this row-sum-==-1 invariant
          exact_facts=(("zone_hot", "onehot_rows"),),
          closure_statics=(("host_ok", "absent"),
                           ("intra_batch_topology", "False"),
                           ("score_bias", "absent"),
                           ("surface", "'tiled'"))),
    Entry("_shardmap_sequential",
          "kubetpu.parallel.shardmap:_shardmap_sequential",
          _shardmap_sequential, keep_sharding=True, static_argnums=(2,),
          static_argnames=("mesh_key",), exact=True,
          closure_statics=(("host_ok", "absent"),
                           ("score_bias", "absent"))),
    Entry("_apply_delta_body",
          "kubetpu.parallel.shardmap:_apply_delta_body",
          _shardmap_delta_donated, tag="donated", donate_argnums=(0,),
          keep_sharding=True, static_argnames=("mesh_key",),
          closure_statics=(("donate", "True"),),
          exempt=(("census/donation-unconsumed",
                   "by design, the shard_map twin of the gspmd scatter's "
                   "audited case: the four vocab-side tables are REPLACED "
                   "wholesale from the replicated delta args, so their "
                   "donated twins have no output to alias into; shard_map "
                   "boundary resharding can further reduce the aliased "
                   "count — the [N,.]/[P,.] residents are the bytes that "
                   "matter and the scatter is correct either way"),),
          exact=True),
    Entry("_apply_delta_body",
          "kubetpu.parallel.shardmap:_apply_delta_body",
          _shardmap_delta_shared, tag="shared", keep_sharding=True,
          static_argnames=("mesh_key",), exact=True,
          closure_statics=(("donate", "False"),)),
]


def registered_qualnames() -> set:
    return {e.qualname for e in ENTRIES}
