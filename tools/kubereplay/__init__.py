"""kubereplay: offline bit-exact re-execution of journaled cycle windows.

The durable cycle journal (kubetpu/utils/journal.py) records every
committed scheduling cycle's exact device-program inputs and outputs.
This tool re-executes any journaled window through the SAME device
programs (models/gang.run_auction / models/sequential
.schedule_sequential) and **bit-matches** the replayed packed placement
vector against the recorded one — the same oracle discipline as the
AOT gate: a divergence is a correctness failure, attributed to the
FIRST divergent cycle with a per-pod decision diff.

Replay reconstructs the scheduler's two device lineages exactly as the
serving loop maintained them:

  * the RESIDENT lineage — ``resync`` records re-upload the journaled
    host mirror (``HostClusterArrays.to_device``), ``delta`` records
    scatter the journaled ``ClusterDelta`` (and the term tables' written
    rows, ``programs.apply_terms_delta``) onto it via
    ``programs.apply_cluster_delta``, ``noop`` records leave it
    untouched;
  * the CHAIN lineage — a ``chain`` record's cluster is the PREVIOUS
    record's replayed auction materialized at the journaled pad buckets
    (``models/gang.materialize_assigned``, ``extend_score_terms=True``).

A corrupt/truncated record (crash, chaos ``journal`` point) or a seq gap
(a dropped write) is skipped with a per-record reason and breaks the
lineage: every subsequent non-anchor record skips with
``broken-lineage`` until the next ``resync`` anchor restores it — the
window degrades, it never aborts.

``--counterfactual`` re-runs the window under a modified profile (score
weights, ``pipelineDepth``) and reports per-cycle
placement divergence plus utilization/spread deltas — every recorded
production trace becomes an eval set (ROADMAP item 3's learned-scorer
substrate).  Counterfactual placements PROPAGATE through the chain
lineage (a changed placement changes the chained cluster downstream),
while delta records replay the FACTUAL environment churn as recorded —
and host plugin / extender verdicts replay from the recorded masks, not
re-executed (documented deviations; see README "Cycle journal &
replay").  ``pipelineDepth`` never enters a device program, so changing
it must report ZERO divergence — the acceptance check that the depth-k
executor's bit-identity contract survives into the replay rig.

Supported surface: single-device cycles (mesh profiles are journaled but
skip with ``unsupported-mesh``); extender-profile cycles are not
journaled at all (host-side selection has no packed device output).
"""

from __future__ import annotations

import pickle
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from kubetpu.utils.journal import INPUT_KINDS, read_records


class ReplayError(RuntimeError):
    pass


def _load_payload(rec: Dict[str, Any]):
    payload = rec.get("input_payload")
    if isinstance(payload, (bytes, bytearray)):
        return pickle.loads(payload)
    return payload


def _apply_counterfactual(rec: Dict[str, Any],
                          counterfactual: Optional[Dict[str, Any]]):
    """cfg for this record's dispatch, with any counterfactual profile
    overrides applied.  ``pipeline_depth`` is accepted and deliberately
    ignored at dispatch — the executor depth never reaches a device
    program (the zero-divergence contract)."""
    cfg = rec["cfg"]
    if not counterfactual:
        return cfg
    weights = counterfactual.get("score_weights")
    if weights:
        unknown = set(weights) - {name for name, _w in cfg.scores}
        if unknown:
            raise ReplayError(
                "counterfactual score plugin(s) not in the recorded "
                "profile: %s (recorded: %s)"
                % (sorted(unknown), [n for n, _ in cfg.scores]))
        cfg = cfg._replace(scores=tuple(
            (name, int(weights.get(name, w))) for name, w in cfg.scores))
    return cfg


def _dispatch(rec: Dict[str, Any], cluster, cfg):
    """Re-execute one journaled cycle's device program; returns the
    result object (``.packed`` is the oracle surface)."""
    import jax
    import jax.numpy as jnp

    # records written before the auction had one kernel path carry the
    # backend that ran them; only the surviving one can be re-executed
    if rec.get("kernel_backend", "lax") != "lax":
        raise ReplayError(
            f"record {rec['seq']} was written by the removed Pallas "
            "kernel backend: the program that produced it is gone")
    batch = rec["batch"]
    rng = jax.random.PRNGKey(int(rec["rng_counter"]))
    host_ok = rec.get("host_ok")
    host_ok = jnp.asarray(host_ok) if host_ok is not None else None
    bias = rec.get("score_bias")
    bias = jnp.asarray(bias) if bias is not None else None
    if rec["mode"] == "gang":
        from kubetpu.models.gang import run_auction
        return run_auction(cluster, batch, cfg, rng, host_ok=host_ok,
                           intra_batch_topology=bool(rec["needs_topo"]),
                           score_bias=bias)
    from kubetpu.models.sequential import schedule_sequential
    return schedule_sequential(
        cluster, batch, cfg, rng,
        hard_pod_affinity_weight=float(rec["hard_pod_affinity_weight"]),
        host_ok=host_ok, start_index=int(rec["start_index"]),
        score_bias=bias)


def _materialize_chain(rec: Dict[str, Any], prev_cluster, prev_batch,
                       prev_res):
    from kubetpu.models.gang import materialize_assigned
    pads = _load_payload(rec)
    if not pads or len(pads) != 2:
        raise ReplayError(f"chain record {rec['seq']} carries no pad "
                          "buckets")
    return materialize_assigned(
        prev_cluster, prev_batch, prev_res.chosen, prev_res.requested,
        prev_res.nz, prev_res.ports_used,
        pad_pods_to=int(pads[0]), pad_terms_to=int(pads[1]),
        extend_score_terms=True,
        hard_pod_affinity_weight=float(rec["hard_pod_affinity_weight"]))


def _apply_delta(rec: Dict[str, Any], resident):
    """Replay one ``delta`` record onto the resident lineage — the exact
    twin of DeltaTensorizer._apply (a term table that crossed whole
    replaced, then the term rows scattered, BEFORE the cluster's scatter;
    donation irrelevant to values, so replay never donates)."""
    import jax
    import jax.numpy as jnp

    from kubetpu.models import programs
    delta, terms = _load_payload(rec)
    if terms is not None:
        whole, deltas = terms
        resident = resident._replace(**{
            field: jax.tree.map(jnp.array, table)
            for field, table in whole.items()})
        ft, st = programs.apply_terms_delta(
            resident.filter_terms, resident.score_terms, *deltas,
            donate=False)
        resident = resident._replace(filter_terms=ft, score_terms=st)
    return programs.apply_cluster_delta(resident, delta, donate=False)


def _placements_of(rec: Dict[str, Any], packed: np.ndarray,
                   node_names: List[str]) -> Dict[str, str]:
    """pod name -> node name ('' unscheduled) from a packed vector — the
    recorded twin lives in rec['placements'] (note: the journal records
    the COMMIT outcome, so a device-chosen pod whose commit failed shows
    '' there; the device-level oracle is the packed vector itself)."""
    B = rec["batch"].valid.shape[0]
    chosen = packed[:B]
    out = {}
    for i, (name, _ns, _uid) in enumerate(rec["pods"]):
        c = int(chosen[i])
        out[name] = (node_names[c]
                     if 0 <= c < len(node_names) else "")
    return out


def _pod_diff(rec: Dict[str, Any], recorded: np.ndarray,
              replayed: np.ndarray,
              node_names: List[str]) -> List[Dict[str, Any]]:
    """Per-pod decision diff between a recorded and a replayed packed
    vector: which pods moved, their feasible-node counts and terminal
    unresolvable flags on each side."""
    B = rec["batch"].valid.shape[0]
    diffs = []
    for i, (name, ns, _uid) in enumerate(rec["pods"]):
        rc, pc = int(recorded[i]), int(replayed[i])
        rn = node_names[rc] if 0 <= rc < len(node_names) else ""
        pn = node_names[pc] if 0 <= pc < len(node_names) else ""
        if (rc, int(recorded[B + i]), int(recorded[2 * B + i])) == \
           (pc, int(replayed[B + i]), int(replayed[2 * B + i])):
            continue
        diffs.append({
            "pod": f"{ns}/{name}",
            "recorded_node": rn, "replayed_node": pn,
            "recorded_n_feasible": int(recorded[B + i]),
            "replayed_n_feasible": int(replayed[B + i]),
            "recorded_unresolvable": bool(recorded[2 * B + i]),
            "replayed_unresolvable": bool(replayed[2 * B + i]),
        })
    return diffs


def _utilization(placements: Dict[str, str]) -> Dict[str, Any]:
    """Placement-distribution summary over a window: how many pods
    landed, across how many nodes, how peaked/spread the per-node load
    is (the counterfactual report's utilization/spread axis)."""
    counts: Dict[str, int] = {}
    for node in placements.values():
        if node:
            counts[node] = counts.get(node, 0) + 1
    vals = list(counts.values())
    if not vals:
        return {"placed": 0, "nodes_used": 0, "max_per_node": 0,
                "mean_per_node": 0.0, "spread_std": 0.0}
    arr = np.asarray(vals, np.float64)
    return {"placed": int(arr.sum()),
            "nodes_used": len(vals),
            "max_per_node": int(arr.max()),
            "mean_per_node": round(float(arr.mean()), 3),
            "spread_std": round(float(arr.std()), 3)}


def replay_journal(directory: str,
                   window: Optional[Tuple[int, int]] = None,
                   counterfactual: Optional[Dict[str, Any]] = None,
                   keep_going: bool = False,
                   max_divergences: int = 16) -> Dict[str, Any]:
    """Replay a journal directory (optionally a ``(start, end)`` seq
    window) and return the report dict the CLI prints.

    Bit-match mode (no counterfactual): every replayed cycle's packed
    vector must equal the recorded one byte-for-byte; the first
    divergence is reported with its per-pod decision diff and — unless
    ``keep_going`` — stops the replay (the oracle has already failed).

    Counterfactual mode: divergence is the MEASUREMENT, not a failure —
    every cycle replays, per-cycle divergence counts and
    utilization/spread deltas are reported, and chains propagate the
    counterfactual placements downstream.

    Lineage warm-up: when a window is requested, replay still begins at
    the nearest ``resync`` anchor at-or-before the window start (the
    preceding records are replayed for state only, not reported)."""
    entries = list(read_records(directory))
    if not entries:
        raise FileNotFoundError(f"no journal records under {directory!r}")

    lo, hi = window if window else (None, None)
    start_at = None
    if lo is not None:
        # the nearest anchor at-or-before the window start
        for seq, rec, skip in entries:
            if seq > lo:
                break
            if rec is not None and rec.get("input") == "resync":
                start_at = seq
        if start_at is None:
            start_at = lo

    report: Dict[str, Any] = {
        "dir": directory,
        "records": len(entries),
        "window": list(window) if window else None,
        "considered": 0, "replayed": 0, "matched": 0,
        "skipped": [], "divergences": [],
        "first_divergence": None,
        "counterfactual": None,
        # the profile/config digests seen in the window: a window that
        # spans more than one digest mixes program configurations (a
        # rollout landed mid-window) — flagged so eval-set consumers can
        # partition by configuration
        "config_digests": [],
    }
    cf_requested = bool(counterfactual)
    cf_overrides: Dict[str, Any] = dict(counterfactual or {})
    cf_divergent_cycles = 0
    cf_diverged_pods = 0
    recorded_plc: Dict[str, str] = {}
    replayed_plc: Dict[str, str] = {}
    digests: List[str] = []

    # Lineage state is PER PROFILE: the scheduler keeps one resident
    # DeltaTensorizer (and one speculative chain) per profile, so a
    # multi-profile journal interleaves independent lineages.  Each
    # entry: {resident, node_names, prev: (seq, cluster, batch, res),
    # need_anchor} — prev additionally requires GLOBAL seq adjacency for
    # chain records (any interleaved cycle of another profile destroys
    # the scheduler's single chain slot, so a non-adjacent parent means
    # the record could not have chained off it).
    class _Lineage:
        __slots__ = ("resident", "node_names", "prev", "need_anchor")

        def __init__(self):
            self.resident = None
            self.node_names: List[str] = []
            self.prev: Optional[Tuple[int, Any, Any, Any]] = None
            self.need_anchor = True

    lineages: Dict[str, _Lineage] = {}
    last_seq: Optional[int] = None
    stop = False

    def skip(seq: int, reason: str, reported: bool) -> None:
        if reported:
            report["skipped"].append({"seq": seq, "reason": reason})

    def break_all() -> None:
        for ln in lineages.values():
            ln.need_anchor = True
            ln.prev = None

    for seq, rec, why in entries:
        if stop:
            break
        if start_at is not None and seq < start_at:
            continue
        if hi is not None and seq > hi:
            break
        reported = lo is None or seq >= lo
        if reported:
            report["considered"] += 1
        if rec is None:
            # the lost record's profile is unknowable: every lineage is
            # suspect until its next anchor
            skip(seq, f"corrupt record: {why}", reported)
            break_all()
            last_seq = seq
            continue
        kind = rec.get("input")
        line = lineages.setdefault(rec.get("profile") or "", _Lineage())
        if last_seq is not None and seq != last_seq + 1:
            # a seq gap (dropped write / evicted file) may hide a delta
            # cycle of ANY profile: no resident lineage is trustworthy
            # (a resync record right after the gap simply re-anchors its
            # own profile's lineage below)
            break_all()
        last_seq = seq
        if rec.get("mesh"):
            skip(seq, "unsupported-mesh", reported)
            line.need_anchor = True
            line.prev = None
            continue
        if kind not in INPUT_KINDS:
            skip(seq, f"unknown input kind {kind!r}", reported)
            line.need_anchor = True
            line.prev = None
            continue
        try:
            if kind == "resync":
                host = _load_payload(rec)
                line.resident = host.to_device()
                line.node_names = list(rec.get("node_names")
                                       or line.node_names)
                line.need_anchor = False
                cluster = line.resident
            elif line.need_anchor:
                skip(seq, "broken-lineage (no resync anchor since the "
                          "last skip/gap)", reported)
                continue
            elif kind == "delta":
                line.resident = _apply_delta(rec, line.resident)
                cluster = line.resident
            elif kind == "noop":
                cluster = line.resident
            else:   # chain
                if line.prev is None or line.prev[0] != seq - 1:
                    skip(seq, "broken-lineage (chain parent not the "
                              "adjacent replayed cycle of this "
                              "profile)", reported)
                    line.need_anchor = True
                    continue
                cluster = _materialize_chain(rec, line.prev[1],
                                             line.prev[2], line.prev[3])
            cfg = _apply_counterfactual(rec, cf_overrides)
            res = _dispatch(rec, cluster, cfg)
            packed = np.asarray(res.packed)
        except ReplayError as e:
            skip(seq, str(e), reported)
            line.need_anchor = True
            line.prev = None
            continue
        line.prev = (seq, cluster, rec["batch"], res)
        node_names = line.node_names
        if not reported:
            continue   # lineage warm-up before the window
        if rec.get("config_digest") and rec["config_digest"] not in digests:
            digests.append(rec["config_digest"])
        report["replayed"] += 1
        recorded = np.asarray(rec["packed"])
        match = (recorded.shape == packed.shape
                 and bool(np.array_equal(recorded, packed)))
        if cf_requested:
            diffs = _pod_diff(rec, recorded, packed, node_names)
            moved = [d for d in diffs
                     if d["recorded_node"] != d["replayed_node"]]
            if moved:
                cf_divergent_cycles += 1
                cf_diverged_pods += len(moved)
            recorded_plc.update(
                _placements_of(rec, recorded, node_names))
            replayed_plc.update(
                _placements_of(rec, packed, node_names))
            if match:
                report["matched"] += 1
            continue
        if match:
            report["matched"] += 1
            continue
        div = {
            "seq": seq,
            "cycle": rec.get("cycle"),
            "links": dict(rec.get("links") or {}),
            "verdicts": dict(rec.get("verdicts") or {}),
            "recorded_rounds": int(recorded[-1]) if recorded.size else 0,
            "replayed_rounds": int(packed[-1]) if packed.size else 0,
            "pod_diff": _pod_diff(rec, recorded, packed, node_names),
        }
        report["divergences"].append(div)
        if report["first_divergence"] is None:
            report["first_divergence"] = div
        if not keep_going or len(report["divergences"]) >= max_divergences:
            stop = True

    report["config_digests"] = digests
    report["bit_match"] = (report["first_divergence"] is None
                          and report["replayed"] > 0)
    if cf_requested:
        rec_util = _utilization(recorded_plc)
        rep_util = _utilization(replayed_plc)
        report["counterfactual"] = {
            "overrides": {k: v for k, v in cf_overrides.items() if v},
            "cycles": report["replayed"],
            "divergent_cycles": cf_divergent_cycles,
            "diverged_pods": cf_diverged_pods,
            "utilization": {
                "recorded": rec_util,
                "counterfactual": rep_util,
                "delta": {k: round(rep_util[k] - rec_util[k], 3)
                          for k in rec_util},
            },
        }
        # counterfactual mode measures divergence, it doesn't gate on it
        report["bit_match"] = None
    return report
