"""Analytic FLOP accounting for the device scheduling programs.

The gang auction's device time is dominated by MXU contractions: the
same-pair matmuls that re-evaluate topology filters/scores per round
([S, P] x [P, N] per active topology key, plus [S, N] x [N, N] pair
registration), the existing-term contractions ([Et, W] x [Et, N]), and the
per-node count matmul.  This module prices those per round, with the round
width following the windowed-residual schedule (round 1 at B, residual
rounds at the window width), so benchmarks can report achieved TFLOP/s and
MFU against the chip's peak (DEVICE_PEAKS below, keyed by device kind).

The model counts the IN-ROUND matmul FLOPs only (2*m*n*k per contraction);
the once-per-cycle precomputation (selector matches, static filters/scores)
and all elementwise work are excluded, so reported MFU is a LOWER bound.

Reference anchor: these matmuls replace the O(pods x nodes) hot loops of
pkg/scheduler/framework/plugins/interpodaffinity/scoring.go:128-199 and
podtopologyspread/scoring.go:108-169.
"""

from __future__ import annotations

from typing import NamedTuple, Optional


class DevicePeaks(NamedTuple):
    flops_per_s: float       # matmul peak for bf16 inputs, f32 accumulate
    hbm_bytes_per_s: float
    hbm_bytes: float
    source: str


# Published per-chip peaks, keyed by the ``device_kind`` jax reports.  A
# part that is not here has no row on purpose: see device_peaks.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        197e12, 819e9, 16 * 2.0 ** 30,
        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
        "16 GB HBM per chip at 819 GB/s"),
}


def device_peaks() -> Optional[DevicePeaks]:
    """Peaks of the device the programs run on, or None when its
    ``device_kind`` is not in DEVICE_PEAKS — the cpu backend included.
    Callers then report no MFU, roofline or fits-in-HBM field at all: a
    run divided by another part's peak is not a measurement."""
    import jax
    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)


def gang_cycle_flops(cluster, batch, cfg, rounds: int,
                     residual_window: int = 512,
                     intra_batch_topology: bool = True) -> float:
    """Matmul FLOPs of one gang-auction cycle (schedule_gang) given the
    executed round count (GangResult.rounds / packed[3B])."""
    N = int(cluster.allocatable.shape[0])
    B = int(batch.valid.shape[0])
    R = int(cluster.allocatable.shape[1])
    TK = int(cluster.topo_pair.shape[1])
    n_keys = len(cfg.active_topo_keys) if cfg.active_topo_keys else TK
    Tr = int(batch.ra.valid.shape[1])
    Ta = int(batch.raa.valid.shape[1])
    Tp = int(batch.pref.valid.shape[1])
    C = int(batch.spread.valid.shape[1])
    C2 = int(batch.spread_soft.valid.shape[1])
    filters = set(cfg.filters)
    scores = {n for n, _ in cfg.scores}
    # mirror schedule_gang's gating exactly: topology filters move into the
    # loop (and the pod axis/filter terms extend by the batch) only when a
    # topology FILTER is configured AND intra_batch_topology is on
    use_sph = "PodTopologySpread" in filters and intra_batch_topology
    use_ipa = "InterPodAffinity" in filters and intra_batch_topology
    intra = use_sph or use_ipa
    P = int(cluster.pod_valid.shape[0]) + (B if intra else 0)
    Et = int(cluster.filter_terms.valid.shape[0]) + (B * Ta if intra else 0)
    Es = int(cluster.score_terms.valid.shape[0])

    def round_flops(W: int) -> float:
        f = 0.0
        if use_sph:
            f += n_keys * (2.0 * W * C * P * N + 2.0 * W * C * N * N)
        if use_ipa:
            f += n_keys * 2.0 * W * (Tr + Ta) * P * N
            f += 2.0 * Et * W * N
        if "InterPodAffinity" in scores:
            f += n_keys * 2.0 * W * Tp * P * N + 2.0 * Es * W * N
        if "PodTopologySpread" in scores:
            f += n_keys * (2.0 * W * C2 * P * N + 2.0 * W * C2 * N * N)
        if "DefaultPodTopologySpread" in scores:
            f += 2.0 * W * P * N
        # fit + resource scorers + normalizes: [W, N, R]-ish elementwise;
        # count one multiply-add sweep as a floor
        f += 2.0 * W * N * R
        return f

    W_resid = min(residual_window or B, B)
    r = max(int(rounds), 0)
    if r == 0:
        return 0.0
    return round_flops(B) + (r - 1) * round_flops(W_resid)
