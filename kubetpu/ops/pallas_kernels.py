"""Fused filter -> score-combine -> auction-propose Pallas megakernel.

The gang auction's round loop (models/gang.py round_step) is a chain of
XLA-fused-but-separate stages: NodeResourcesFit + NodePorts feasibility
materialize a [B, N] mask in HBM, run_scores materializes the [B, N]
weighted score matrix, and the propose step re-reads both to pick each
pod's argmax node.  Every auction round pays that HBM round trip
(auction_rounds_max is 4-13 at BENCH/MULTICHIP shapes), and the serial
round dependency — not FLOPs — bounds cycle latency.

This module is the Pallas beachhead for ROADMAP item 3: ONE kernel, tiled
over the node axis, that per [TB, TN] tile

  (a) computes the feasibility mask (static filter mask AND'd with the
      fit verdict against the round's committed usage and the hostPort
      conflict against the round's registered ports),
  (b) combines the weighted plugin scores (resource scorers from the
      evolving requested/nonzero carries; normalization-family scorers
      from per-pod statistics accumulated in a first grid phase), and
  (c) runs the propose step of the bidding round (masked score max +
      selectHost gumbel tie-break argmax),

with the per-tile [B, N_tile] score block living entirely in VMEM: per
round, HBM traffic is the carry reads plus three [B]-sized outputs — the
[B, N] mask/score intermediates never exist off-chip.  Admission stays on
the existing segmented-reduce logic in models/gang.py (it is O(B), not
O(B*N)), as does round 0 (whose [B, N] feasibility IS a GangResult
diagnostic output).  What remains for a later PR is full auction-LOOP
residency: the while_loop still lives at lax level, so score tiles are
re-streamed per round rather than pinned across rounds.

Bit-match oracle contract
-------------------------
The lax path is the oracle: for any supported (cfg, batch) this kernel's
(prop, active, best) are BIT-IDENTICAL to round_step's propose half.
Three properties make that tractable:

  * selectHost tie-breaks decompose: jax.random.categorical(key, logits)
    == argmax(gumbel(key, shape) + logits), and with the auction's
    0 / -2**62 logits the sum is exactly ``where(tie, gumbel, -2**62)``
    in f32 — so the gumbel matrix is precomputed ONCE from the same
    fold_in keys and the kernel only needs a cross-tile argmax whose
    first-index tie-break matches jnp.argmax.
  * every cross-node reduction the supported score family needs is
    either a float max/min (exactly associative) or a sum of
    integer-valued f32 (exact in any order below 2**24): per-pod
    normalization stats accumulate tile-by-tile without rounding drift.
  * everything else is elementwise, reusing the SAME jnp formula
    helpers as the lax kernels (balanced_formula/least_formula/...), so
    each element sees an identical f32 op sequence.

Supported surface (see kubetpu/utils/pallas_backend.unsupported_reason):
intra_batch_topology=False rounds (the host already routes term-free
batches there), score plugins whose feasibility dependence is per-pod
stats — the full default family.  PodTopologySpread soft scoring is
supported via its no-soft-constraints constant path (MaxNodeScore on
every feasible node), which is exactly what a term-free batch evaluates
to; batches carrying soft constraints fall back in the dispatcher (the
scheduler's needs_topo gate routes them away anyway, and the
schedule_gang wrapper's host-side batch inspection catches direct
callers — reason "soft-spread-constraints").
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import kernels as K
from ..state.tensors import CH_CPU, CH_MEM, CH_PODS, N_FIXED_CHANNELS
from ..utils.intern import pow2_bucket

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = float(-2**62)
_BIG = float(2**62)
MAX_NODE_SCORE = K.MAX_NODE_SCORE

# score plugins whose raw matrix is round-invariant under
# intra_batch_topology=False and enters the kernel as a plane
_PLANE_OF = {
    "ImageLocality": "raw:ImageLocality",
    "NodeAffinity": "raw:NodeAffinity",
    "NodePreferAvoidPods": "raw:NodePreferAvoidPods",
    "TaintToleration": "raw:TaintToleration",
    "InterPodAffinity": "ipa_raw",
    "DefaultPodTopologySpread": "dps_raw",
}

# the full supported score family; anything else falls back to lax
SUPPORTED_SCORES = frozenset(_PLANE_OF) | frozenset({
    "NodeResourcesBalancedAllocation",
    "NodeResourcesLeastAllocated",
    "NodeResourcesMostAllocated",
    "PodTopologySpread",  # no-soft-constraints constant path (see above)
})

_LANE = 128  # TPU lane width: the natural node-tile quantum


def plane_order(cfg, has_bias: bool) -> Tuple[str, ...]:
    """Static plane layout of the stacked [S, B, N] input: score raws in
    cfg.scores order, then the optional host score bias, then the
    selectHost gumbel matrix (always last)."""
    names = []
    for name, _ in cfg.scores:
        key = _PLANE_OF.get(name)
        if key is not None and key not in names:
            names.append(key)
    if has_bias:
        names.append("bias")
    names.append("gumbel")
    return tuple(names)


def build_bundle(cluster, batch, cfg, static_ok, ports_ok0, score_pre,
                 score_bias, gumbel) -> Dict[str, jnp.ndarray]:
    """Precompute the megakernel's round-invariant inputs, once per
    auction (traced inside _schedule_gang).  All [B, N] planes here are
    assignment-independent under intra_batch_topology=False: the pod axis
    is frozen during the loop, so interpod/default-spread raws are
    round-invariant even though their lax twins recompute per round."""
    B = batch.req.shape[0]
    planes: Dict[str, jnp.ndarray] = {}
    ipa_any = jnp.zeros((B,), bool)
    for name, _ in cfg.scores:
        if name == "InterPodAffinity" and "ipa_raw" not in planes:
            raw, any_counts = K.interpod_score_raw(
                cluster, batch, pre=score_pre.get("interpod_score"),
                active_keys=cfg.active_keys)
            planes["ipa_raw"] = raw
            ipa_any = any_counts[:, 0]
        elif name == "DefaultPodTopologySpread" and "dps_raw" not in planes:
            planes["dps_raw"] = K.default_spread_score(
                cluster, batch, match_ns=score_pre.get("default_spread"))
        elif name in _PLANE_OF and _PLANE_OF[name] not in planes:
            planes[_PLANE_OF[name]] = score_pre["raw:" + name]
    if score_bias is not None:
        planes["bias"] = score_bias
    planes["gumbel"] = gumbel
    order = plane_order(cfg, score_bias is not None)
    stack = jnp.stack([planes[k].astype(jnp.float32) for k in order])
    zone = cluster.zone_hot
    if zone.shape[1] == 0:
        zone = jnp.zeros((zone.shape[0], 1), jnp.float32)
    return dict(
        planes=stack,                         # [S, B, N] f32
        mask=static_ok & ports_ok0,           # [B, N] bool
        ipa_any=ipa_any,                      # [B] bool
        skip=batch.spread_skip,               # [B] bool
        breq=batch.req,                       # [B, R] f32
        bnz=batch.nonzero_req,                # [B, 2] f32
        bports=batch.ports_hot,               # [B, P] f32
        alloc=cluster.allocatable,            # [N, R] f32 (node side)
        zone=zone,                            # [N, Z] f32 (node side)
    )


_POD_SIDE = ("planes", "mask", "ipa_any", "skip", "breq", "bnz", "bports")


def gather_bundle(bundle: Dict[str, jnp.ndarray], rows: jnp.ndarray,
                  B: int) -> Dict[str, jnp.ndarray]:
    """Row-gather the pod-side bundle tensors for a windowed sub-round.
    Sentinel rows (>= B) clip to row B-1; the caller's `live` vector is
    False there, so the kernel proposes the no-op segment for them."""
    rsafe = jnp.clip(rows, 0, B - 1)
    out = dict(bundle)
    for k in _POD_SIDE:
        axis = 1 if k == "planes" else 0
        out[k] = jnp.take(bundle[k], rsafe, axis=axis)
    return out


def kernel_layout(bundle: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Re-lay the round-invariant bundle the way Mosaic tiles it, once
    per auction: the node axis is the LANE axis everywhere, so it is
    zero-padded to a whole number of 128-wide tiles (the kernel masks
    columns >= N itself) and the node-side tables are transposed to
    [channel, node] rows — a channel then reads as one sublane row that
    broadcasts down the pod axis, never a lane->sublane relayout."""
    N = bundle["alloc"].shape[0]
    pad = -N % _LANE

    def padn(x, axis):
        if not pad:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths)

    out = dict(bundle)
    out["planes"] = padn(bundle["planes"], 2)
    out["mask"] = padn(bundle["mask"], 1)
    out["alloc"] = padn(bundle["alloc"].T, 1)     # [R, Npad]
    out["zone"] = padn(bundle["zone"].T, 1)       # [Z, Npad]
    return out


class _Layout(NamedTuple):
    """Static kernel layout, derived once per trace."""
    scores: Tuple[Tuple[str, float], ...]
    planes: Tuple[str, ...]
    use_fit: bool
    use_ports: bool
    stat_cols: Tuple[Tuple[str, int], ...]
    n_stats: int
    W: int
    N: int
    R: int
    P: int
    Z: int
    TB: int
    TN: int
    NT: int


# int8/bool blocks tile (32, 128): the smallest pod block every operand
# dtype accepts
_MIN_TB = 32


def _layout(cfg, has_bias: bool, W: int, N: int, R: int, P: int,
            Z: int) -> _Layout:
    filters = set(cfg.filters)
    cols = []
    for name, _ in cfg.scores:
        if name == "NodeAffinity":
            cols.append("max_na")
        elif name == "TaintToleration":
            cols.append("max_tt")
        elif name == "InterPodAffinity":
            cols += ["max_ip", "min_ip"]
        elif name == "DefaultPodTopologySpread":
            cols += ["max_dps", "havez"]
    cols += ["act", "best", "hh"]
    stat_cols = tuple((c, i) for i, c in enumerate(dict.fromkeys(cols)))
    TB = min(_LANE, max(_MIN_TB, pow2_bucket(max(W, 1), 1)))
    return _Layout(
        scores=tuple((n, float(w)) for n, w in cfg.scores),
        planes=plane_order(cfg, has_bias),
        use_fit="NodeResourcesFit" in filters,
        use_ports="NodePorts" in filters,
        stat_cols=stat_cols, n_stats=len(stat_cols),
        W=W, N=N, R=R, P=P, Z=Z, TB=TB, TN=_LANE,
        NT=-(-N // _LANE))


class Buf(NamedTuple):
    """One kernel buffer: a BlockSpec'd input/output or a VMEM scratch.
    ``shape`` is the BLOCK shape for in/out (full shape for scratch);
    ``index`` gives the grid->block index map per dim ("b" = pod-block
    axis, "n" = node-tile axis, "z" = pinned 0)."""
    name: str
    kind: str                  # "in" | "out" | "scratch"
    shape: Tuple[int, ...]
    dtype: str
    index: Tuple[str, ...] = ()


# columns of the per-pod int32 flag block
_FLAG_LIVE, _FLAG_SKIP, _FLAG_IPA_ANY = range(3)


def kernel_buffers(L: _Layout, WB: int) -> Tuple[Buf, ...]:
    """The kernel's full buffer table, in pallas_call operand order.
    Single source of truth: propose() builds its BlockSpecs/out_shape/
    scratch_shapes from this, and tools/kubeexact computes the static
    VMEM budget from the same rows — the gate can never drift from the
    traced program.  Every buffer is 2-D or 3-D with the node axis (or a
    full-width channel axis) minor: per-pod vectors are [TB, 1] columns
    and node tables are [channel, TN] rows, the two shapes Mosaic
    broadcasts without a relayout."""
    Wpad = WB * L.TB
    return (
        Buf("planes", "in", (len(L.planes), L.TB, L.TN), "float32",
            ("z", "b", "n")),
        Buf("mask", "in", (L.TB, L.TN), "bool", ("b", "n")),
        Buf("alloc", "in", (L.R, L.TN), "float32", ("z", "n")),
        Buf("zone", "in", (L.Z, L.TN), "float32", ("z", "n")),
        Buf("req", "in", (L.R, L.TN), "float32", ("z", "n")),
        Buf("nz", "in", (2, L.TN), "float32", ("z", "n")),
        Buf("ports_used", "in", (L.P, L.TN), "float32", ("z", "n")),
        Buf("breq", "in", (L.TB, L.R), "float32", ("b", "z")),
        Buf("bnz", "in", (L.TB, 2), "float32", ("b", "z")),
        Buf("bports", "in", (L.TB, L.P), "float32", ("b", "z")),
        Buf("flags", "in", (L.TB, 3), "int32", ("b", "z")),
        Buf("prop", "out", (L.TB, 1), "int32", ("b", "z")),
        Buf("best", "out", (L.TB, 1), "float32", ("b", "z")),
        Buf("act", "out", (L.TB, 1), "int32", ("b", "z")),
        Buf("stats", "scratch", (Wpad, L.n_stats), "float32"),
        Buf("czone", "scratch", (Wpad, L.Z), "float32"),
        Buf("idxs", "scratch", (Wpad, 1), "int32"),
    )


def _make_kernel(L: _Layout):
    """Build the kernel body for one static layout.  Phase 0 sweeps the
    node tiles accumulating the per-pod normalization statistics; phase 1
    re-derives feasibility (VPU recompute is cheaper than an HBM round
    trip), combines the weighted scores and folds the propose argmax."""
    col = {name: i for name, i in L.stat_cols}
    plane = {name: i for i, name in enumerate(L.planes)}

    def kernel(planes_ref, mask_ref, alloc_ref, zone_ref, req_ref, nz_ref,
               pu_ref, breq_ref, bnz_ref, bports_ref, flags_ref,
               prop_ref, best_ref, act_ref, stats, czone, idxs):
        p = pl.program_id(0)
        b = pl.program_id(1)
        n = pl.program_id(2)
        sl = pl.ds(pl.multiple_of(b * L.TB, L.TB), L.TB)
        col_ok = (n * L.TN + jax.lax.broadcasted_iota(
            jnp.int32, (L.TB, L.TN), 1)) < L.N
        flags = flags_ref[...]

        def flag(i):                       # [TB, 1] bool
            return flags[:, i:i + 1] != 0

        def stat(name):                    # [TB, 1] f32
            return stats[sl, col[name]:col[name] + 1]

        def feas_tile():
            f = mask_ref[...] & flag(_FLAG_LIVE) & col_ok
            breq = breq_ref[...]
            if L.use_fit:
                alloc = alloc_ref[...]
                used = req_ref[...]

                def free_ok(r):            # [1, TN] >= [TB, 1] + [1, TN]
                    return (alloc[r:r + 1, :]
                            >= breq[:, r:r + 1] + used[r:r + 1, :])

                pods_ok = free_ok(CH_PODS)
                res_ok = jnp.ones((L.TB, L.TN), bool)
                zero_req = jnp.ones((L.TB, 1), bool)
                for r in range(L.R):
                    if r == CH_PODS:
                        continue
                    if r < N_FIXED_CHANNELS:
                        res_ok = res_ok & free_ok(r)
                    else:
                        res_ok = res_ok & (free_ok(r)
                                           | (breq[:, r:r + 1] <= 0))
                    zero_req = zero_req & (breq[:, r:r + 1] == 0)
                f = f & pods_ok & (zero_req | res_ok)
            if L.use_ports:
                conflict = jnp.dot(bports_ref[...], pu_ref[...],
                                   preferred_element_type=jnp.float32) > 0.5
                f = f & ~conflict
            return f

        def resource_fracs():
            bnz = bnz_ref[...]
            nzc = nz_ref[...]
            alloc = alloc_ref[...]
            req_cpu = nzc[0:1, :] + bnz[:, 0:1]
            req_mem = nzc[1:2, :] + bnz[:, 1:2]
            alloc_cpu = jnp.broadcast_to(alloc[CH_CPU:CH_CPU + 1, :],
                                         (L.TB, L.TN))
            alloc_mem = jnp.broadcast_to(alloc[CH_MEM:CH_MEM + 1, :],
                                         (L.TB, L.TN))
            return req_cpu, req_mem, alloc_cpu, alloc_mem

        def zone_tile():                   # [Z, TN], padded columns zeroed
            cok = (n * L.TN + jax.lax.broadcasted_iota(
                jnp.int32, (L.Z, L.TN), 1)) < L.N
            return jnp.where(cok, zone_ref[...], 0.0)

        def rowmax(x):
            return jnp.max(x, axis=1, keepdims=True)

        # ---- phase 0: per-pod normalization statistics -----------------
        @pl.when(p == 0)
        def _():
            f = feas_tile()

            def acc(name, tile_val, comb):
                c = col[name]

                @pl.when(n == 0)
                def _():
                    stats[sl, c:c + 1] = tile_val

                @pl.when(n > 0)
                def _():
                    stats[sl, c:c + 1] = comb(stats[sl, c:c + 1], tile_val)

            # bool -> f32 cast, not where(f, 1.0, 0.0): two python-float
            # branches commit the default float dtype, which is f64
            # wherever x64 is enabled (census/f64-promotion)
            acc("act", rowmax(f.astype(jnp.float32)), jnp.maximum)
            if "max_na" in col:
                raw = planes_ref[plane["raw:NodeAffinity"]]
                acc("max_na", rowmax(jnp.where(f, raw, _NEG)), jnp.maximum)
            if "max_tt" in col:
                raw = planes_ref[plane["raw:TaintToleration"]]
                acc("max_tt", rowmax(jnp.where(f, raw, _NEG)), jnp.maximum)
            if "max_ip" in col:
                raw = planes_ref[plane["ipa_raw"]]
                acc("max_ip", rowmax(jnp.where(f, raw, _NEG)), jnp.maximum)
                acc("min_ip",
                    jnp.min(jnp.where(f, raw, _BIG), axis=1, keepdims=True),
                    jnp.minimum)
            if "max_dps" in col:
                raw = planes_ref[plane["dps_raw"]]
                zt = zone_tile()
                acc("max_dps", rowmax(jnp.where(f, raw, _NEG)), jnp.maximum)
                has_zone = jnp.max(zt, axis=0, keepdims=True) > 0  # [1, TN]
                acc("havez", rowmax((f & has_zone).astype(jnp.float32)),
                    jnp.maximum)
                # [TB, TN] x [Z, TN]^T: per-zone sums of this tile
                cz = jax.lax.dot_general(
                    jnp.where(f, raw, 0.0), zt, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)

                @pl.when(n == 0)
                def _():
                    czone[sl, :] = cz

                @pl.when(n > 0)
                def _():
                    czone[sl, :] = czone[sl, :] + cz

        # ---- phase 1: score combine + propose --------------------------
        @pl.when(p == 1)
        def _():
            f = feas_tile()
            total = jnp.zeros((L.TB, L.TN), jnp.float32)
            for name, weight in L.scores:
                if name == "NodeResourcesBalancedAllocation":
                    s = K.balanced_formula(*resource_fracs())
                elif name == "NodeResourcesLeastAllocated":
                    rc, rm, ac, am = resource_fracs()
                    s = K._idiv(K.least_formula(rc, ac) * 1.0
                                + K.least_formula(rm, am) * 1.0, 2.0)
                elif name == "NodeResourcesMostAllocated":
                    rc, rm, ac, am = resource_fracs()
                    s = K._idiv(K.most_formula(rc, ac) * 1.0
                                + K.most_formula(rm, am) * 1.0, 2.0)
                elif name == "ImageLocality":
                    s = planes_ref[plane["raw:ImageLocality"]]
                elif name == "NodePreferAvoidPods":
                    s = planes_ref[plane["raw:NodePreferAvoidPods"]]
                elif name == "NodeAffinity":
                    raw = planes_ref[plane["raw:NodeAffinity"]]
                    max_c = jnp.maximum(stat("max_na"), 0.0)
                    scaled = K._idiv(MAX_NODE_SCORE * raw,
                                     jnp.maximum(max_c, 1.0))
                    s = jnp.where(max_c > 0, scaled, 0.0)
                elif name == "TaintToleration":
                    raw = planes_ref[plane["raw:TaintToleration"]]
                    max_c = jnp.maximum(stat("max_tt"), 0.0)
                    scaled = MAX_NODE_SCORE - K._idiv(
                        MAX_NODE_SCORE * raw, jnp.maximum(max_c, 1.0))
                    s = jnp.where(max_c > 0, scaled, MAX_NODE_SCORE)
                elif name == "InterPodAffinity":
                    raw = planes_ref[plane["ipa_raw"]]
                    max_c = jnp.maximum(stat("max_ip"), 0.0)
                    min_c = jnp.minimum(stat("min_ip"), 0.0)
                    diff = max_c - min_c
                    norm = jnp.where(
                        diff > 0,
                        K._idiv(MAX_NODE_SCORE * (raw - min_c),
                                jnp.maximum(diff, 1.0)), 0.0)
                    s = jnp.where(flag(_FLAG_IPA_ANY), norm, raw)
                elif name == "PodTopologySpread":
                    # no-soft-constraints constant path (scoring.go
                    # maxScore==0): MaxNodeScore on every feasible node
                    s = jnp.where(f, MAX_NODE_SCORE, 0.0)
                elif name == "DefaultPodTopologySpread":
                    raw = planes_ref[plane["dps_raw"]]
                    zt = zone_tile()
                    max_node = jnp.maximum(stat("max_dps"), 0.0)
                    f_score = jnp.where(
                        max_node > 0,
                        MAX_NODE_SCORE * (max_node - raw)
                        / jnp.maximum(max_node, 1.0),
                        MAX_NODE_SCORE)
                    cz = czone[sl, :]
                    max_zone = jnp.maximum(rowmax(cz), 0.0)
                    nzc = jnp.dot(cz, zt,
                                  preferred_element_type=jnp.float32)
                    zone_score = jnp.where(
                        max_zone > 0,
                        MAX_NODE_SCORE * (max_zone - nzc)
                        / jnp.maximum(max_zone, 1.0),
                        MAX_NODE_SCORE)
                    with_zone = (f_score * (1.0 - K.ZONE_WEIGHTING)
                                 + K.ZONE_WEIGHTING * zone_score)
                    havez = stat("havez") > 0
                    has_zone = jnp.max(zt, axis=0, keepdims=True) > 0
                    out = jnp.where(havez & has_zone, with_zone, f_score)
                    out = jnp.floor(out)
                    s = jnp.where(flag(_FLAG_SKIP), 0.0, out)
                else:  # pragma: no cover - unsupported_reason() gates this
                    raise ValueError("pallas backend: unsupported score "
                                     "kernel %s" % name)
                total = total + jnp.where(f, s, 0.0) * weight
            if "bias" in plane:
                total = total + planes_ref[plane["bias"]]
            gum = planes_ref[plane["gumbel"]]
            # blessed gumbel decomposition (ops/kernels.py): same tuple
            # the shard_map tiled surface folds across the node axis
            tile_best, tile_h, tile_arg = K.gumbel_tiebreak_argmax(
                total, f, gum, n * L.TN, _NEG, keepdims=True)

            @pl.when(n == 0)
            def _():
                stats[sl, col["best"]:col["best"] + 1] = tile_best
                stats[sl, col["hh"]:col["hh"] + 1] = tile_h
                idxs[sl, :] = tile_arg

            @pl.when(n > 0)
            def _():
                rb = stat("best")
                rh = stat("hh")
                ri = idxs[sl, :]
                # first-index tie-break: update only on STRICT improvement
                # (earlier tiles, and jnp.argmax within a tile, keep the
                # lowest index on exact equality — matching the oracle)
                upd = tile_best > rb
                updh = (tile_best == rb) & (tile_h > rh)
                stats[sl, col["best"]:col["best"] + 1] = jnp.where(
                    upd, tile_best, rb)
                stats[sl, col["hh"]:col["hh"] + 1] = jnp.where(
                    upd, tile_h, jnp.where(updh, tile_h, rh))
                idxs[sl, :] = jnp.where(upd, tile_arg,
                                        jnp.where(updh, tile_arg, ri))

            @pl.when(n == L.NT - 1)
            def _():
                act = stat("act") > 0
                best_ref[...] = stat("best")
                prop_ref[...] = jnp.where(act, idxs[sl, :], L.N).astype(
                    jnp.int32)
                act_ref[...] = act.astype(jnp.int32)

    return kernel


def propose(bundle: Dict[str, jnp.ndarray], cfg, live: jnp.ndarray,
            req: jnp.ndarray, nz: jnp.ndarray, ports_used: jnp.ndarray,
            n_nodes: int, interpret: bool):
    """One fused propose step -> (prop [W] i32 in [0, N] with N = no-op,
    active [W] bool, best [W] f32) — bit-identical to the lax round's
    propose half for supported configurations.  ``bundle`` is in
    kernel_layout() form; the per-round carries (req, nz, ports_used)
    arrive as the auction keeps them, [N, channel], and are re-laid
    here — they are the only node-side tensors that change per round."""
    W = int(live.shape[0])
    N = int(n_nodes)
    R = int(bundle["alloc"].shape[0])
    P = int(bundle["bports"].shape[1])
    Z = int(bundle["zone"].shape[0])
    has_bias = bundle["planes"].shape[0] == len(plane_order(cfg, True))
    L = _layout(cfg, has_bias, W, N, R, P, Z)
    WB = -(-W // L.TB)
    Wpad = WB * L.TB
    Npad = L.NT * L.TN

    def padw(x, axis=0):
        if Wpad == x.shape[axis]:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, Wpad - x.shape[axis])
        return jnp.pad(x, widths)

    def node_rows(x):                      # [N, C] carry -> [C, Npad]
        return jnp.pad(x.T, [(0, 0), (0, Npad - N)])

    flags = jnp.stack([live, bundle["skip"], bundle["ipa_any"]],
                      axis=1).astype(jnp.int32)
    kernel = _make_kernel(L)
    grid = (2, WB, L.NT)
    bufs = kernel_buffers(L, WB)

    def spec(bf: Buf) -> "pl.BlockSpec":
        dims = bf.index
        return pl.BlockSpec(
            bf.shape,
            lambda p, b, n, dims=dims: tuple(
                b if t == "b" else n if t == "n" else 0 for t in dims))

    # an out's full shape tiles its block over the grid axes it indexes
    def full(bf: Buf) -> Tuple[int, ...]:
        mult = {"b": WB, "n": L.NT, "z": 1}
        return tuple(d * mult[t] for d, t in zip(bf.shape, bf.index))

    prop, best, act = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec(bf) for bf in bufs if bf.kind == "in"],
        out_specs=tuple(spec(bf) for bf in bufs if bf.kind == "out"),
        out_shape=tuple(
            jax.ShapeDtypeStruct(full(bf), jnp.dtype(bf.dtype))
            for bf in bufs if bf.kind == "out"),
        scratch_shapes=[
            pltpu.VMEM(bf.shape, jnp.dtype(bf.dtype))
            for bf in bufs if bf.kind == "scratch"],
        interpret=interpret,
    )(
        padw(bundle["planes"], 1), padw(bundle["mask"]),
        bundle["alloc"], bundle["zone"],
        node_rows(req), node_rows(nz), node_rows(ports_used),
        padw(bundle["breq"]), padw(bundle["bnz"]), padw(bundle["bports"]),
        padw(flags),
    )
    return prop[:W, 0], act[:W, 0] != 0, best[:W, 0]
