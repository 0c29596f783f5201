"""AOT executable artifacts (kubetpu/utils/aot.py + tools/kubeaot).

The acceptance round trip: a serving program captured at build time
(jit.lower().compile() + serialize_executable) must deserialize, accept
the census manifest's call form (the same builders produce the inputs),
and produce results BIT-IDENTICAL to the traced path — with the capture's
lowering sha256 equal to the committed COMPILE_MANIFEST.json row's (the
build-time oracle: same StableHLO in, same placements out).  Around that:
signature normalization, env-drift fallback, preload/aot-load flight
spans, the ladder-pruning bucket logic, the pure-JSON index gate, and the
cold_restart_s NORTHSTAR gate arithmetic.
"""
import json
import os

import jax
import numpy as np
import pytest

from kubetpu.utils import aot

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


# ---------------------------------------------------------- round trip


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """ONE cold capture of _schedule_gang at the manifest's smallest rung
    (n8_b8), shared by the round-trip tests — the registry builders
    produce the exact serving input structures, and cache_disabled +
    clear_caches reproduce the census's cold-cache sha discipline."""
    from kubetpu.utils.compilation import cache_disabled
    from tools.kubecensus.registry import ENTRIES, build_world

    e = next(en for en in ENTRIES
             if en.program == "_schedule_gang" and not en.tag)
    rung = e.ladder[0]
    w = build_world(rung)
    fn, args, kwargs = e.build(w)
    root = str(tmp_path_factory.mktemp("aot-store"))
    rt = aot.AotRuntime(aot.AotStore(root), mode="capture",
                        family="census")
    with cache_disabled():
        jax.clear_caches()
        row = rt.capture_call(e.program, fn, args, kwargs,
                              static_argnums=e.static_argnums,
                              static_argnames=e.static_argnames,
                              row_name="%s@%s" % (e.program, rung.name),
                              variant=rung.name)
    rt.flush_index()
    return {"root": root, "row": row, "entry": e, "rung": rung,
            "fn": fn, "args": args, "kwargs": kwargs}


def test_capture_sha_matches_committed_manifest(captured):
    """The bit-identity oracle: the artifact was compiled from the SAME
    StableHLO the census audited — its lowering sha256 equals the
    committed manifest row's."""
    from tools.kubecensus.manifest import load_manifest, row_id
    assert captured["row"] is not None, "capture failed"
    rows = load_manifest()
    assert rows, "no committed COMPILE_MANIFEST.json"
    rid = "%s@%s" % (captured["entry"].program, captured["rung"].name)
    mrow = next(r for r in rows if row_id(r) == rid)
    assert captured["row"]["lowering_sha256"] == mrow["lowering_sha256"]


def test_roundtrip_deserializes_and_matches_traced_bitwise(captured):
    """A fresh serve runtime over the captured store: the dispatch must
    HIT (deserialize-and-load, no trace), accept the manifest-form call
    (same builders, so the executable's input-pytree check passes), and
    return leaves bit-identical to the jit/traced path."""
    e = captured["entry"]
    rt = aot.AotRuntime(aot.AotStore(captured["root"]), mode="serve")
    assert rt.disabled_reason is None
    got = rt.dispatch(e.program, captured["fn"], captured["args"],
                      captured["kwargs"],
                      static_argnums=e.static_argnums,
                      static_argnames=e.static_argnames)
    st = rt.stats()
    assert st["hits"] == 1 and st["misses"] == 0 and st["loads"] == 1
    want = captured["fn"](*captured["args"], **captured["kwargs"])
    got_l, got_t = jax.tree_util.tree_flatten(got)
    want_l, want_t = jax.tree_util.tree_flatten(want)
    assert got_t == want_t
    for g, w in zip(got_l, want_l):
        assert np.array_equal(np.asarray(g), np.asarray(w)), \
            "aot result diverged from the traced program"


def test_second_dispatch_uses_resident_executable(captured):
    """After the first load the executable is resident: no second load."""
    e = captured["entry"]
    rt = aot.AotRuntime(aot.AotStore(captured["root"]), mode="serve")
    for _ in range(2):
        rt.dispatch(e.program, captured["fn"], captured["args"],
                    captured["kwargs"], static_argnums=e.static_argnums,
                    static_argnames=e.static_argnames)
    st = rt.stats()
    assert st["hits"] == 2 and st["loads"] == 1


def test_preload_loads_up_front_and_emits_flight_spans(captured):
    """Scheduler.prewarm's fast path: preload() deserializes every indexed
    artifact before the first cycle, and each load lands an ``aot-load``
    span (seconds + hit) on the open cycle record — the satellite that
    makes restart cost visible in traceview//debug/flightz."""
    from kubetpu.utils import trace as utrace
    rt = aot.AotRuntime(aot.AotStore(captured["root"]), mode="serve")
    fr = utrace.FlightRecorder(capacity=4)
    rec = fr.begin_cycle("prewarm")
    with rec.span("prewarm", mode="aot-artifact"):
        report = rt.preload(family=None)
    fr.commit_cycle(rec)
    assert report and all(r["ok"] for r in report)
    assert rt.stats()["loads"] == len(report)
    names = [s.name for s in rec.spans()]
    assert "prewarm" in names and "aot-load" in names
    aot_spans = [s for s in rec.spans() if s.name == "aot-load"]
    assert all(s.args.get("hit") for s in aot_spans)
    assert all(s.args.get("seconds") is not None for s in aot_spans)


# ------------------------------------------------------------ signatures


def test_call_signature_drops_none_default_kwargs():
    """f(x) and f(x, host_ok=None) must key AND call identically — every
    seamed program's optional arrays default to None, and a deserialized
    executable validates its input pytree exactly."""
    @jax.jit
    def f(x, host_ok=None):
        return x + 1 if host_ok is None else x + host_ok

    x = np.ones((4,), np.float32)
    k1, d1, kw1, _, _ = aot.call_signature("f", f, (x,), {})
    k2, d2, kw2, _, _ = aot.call_signature("f", f, (x,),
                                           {"host_ok": None})
    assert k1 == k2
    assert kw1 == {} and kw2 == {}


def test_call_signature_fills_static_defaults():
    """An unpassed static kwarg resolves to the function default, exactly
    as jit's cache key does — f(x) and f(x, n=3) key identically."""
    import functools

    @functools.partial(jax.jit, static_argnames=("n",))
    def f(x, n=3):
        return x * n

    # NB the declared-defaults lookup is cached BY PROGRAM NAME (the
    # seams each own a unique name); tests must not share one
    x = np.ones((4,), np.float32)
    k1 = aot.call_signature("f_static", f, (x,), {},
                            static_argnames=("n",))[0]
    k2 = aot.call_signature("f_static", f, (x,), {"n": 3},
                            static_argnames=("n",))[0]
    k3 = aot.call_signature("f_static", f, (x,), {"n": 4},
                            static_argnames=("n",))[0]
    assert k1 == k2
    assert k1 != k3


def test_signature_distinguishes_shapes():
    @jax.jit
    def f(x):
        return x + 1

    k4 = aot.call_signature("f", f, (np.ones((4,), np.float32),), {})[0]
    k8 = aot.call_signature("f", f, (np.ones((8,), np.float32),), {})[0]
    assert k4 != k8


def test_signature_tags_multi_device_sharding():
    """A mesh profile routes through the SAME seamed Python entries with
    sharded arrays — those calls must never key to an artifact compiled
    for single-device inputs (the executable would reject them)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices("cpu")
    if len(devs) < 2:
        pytest.skip("needs multi-device CPU")

    @jax.jit
    def f(x):
        return x + 1

    host = np.ones((8, 8), np.float32)
    mesh = Mesh(np.array(devs[:2]).reshape(2), ("nodes",))
    sharded = jax.device_put(host, NamedSharding(mesh, P("nodes")))
    k_host = aot.call_signature("f_shard", f, (host,), {})[0]
    k_single = aot.call_signature("f_shard", f,
                                  (jax.device_put(host, devs[0]),), {})[0]
    k_mesh = aot.call_signature("f_shard", f, (sharded,), {})[0]
    # single-device placement keys like a numpy host (committed index
    # keys stay valid); the mesh placement keys differently
    assert k_host == k_single
    assert k_mesh != k_host


def test_rejected_executable_call_falls_back(tmp_path):
    """A loaded executable that REJECTS the call (sharding/layout the
    signature missed) must fall back to the jit and remember the miss —
    arming artifacts is never worse than serving disarmed."""
    @jax.jit
    def f(x):
        return x + 3

    x = np.ones((2,), np.float32)
    key = aot.call_signature("f_reject", f, (x,), {})[0]
    store = aot.AotStore(str(tmp_path))
    store.write_index(aot.env_signature(), [])
    rt = aot.AotRuntime(store, mode="serve")

    def raiser(*a, **k):
        raise RuntimeError("input sharding mismatch")

    with rt._lock:
        rt._execs[key] = raiser
    out = rt.dispatch("f_reject", f, (x,), {})
    assert np.array_equal(np.asarray(out), x + 3)
    st = rt.stats()
    assert st["misses"] == 1 and st["hits"] == 0
    # the key is remembered: the second call skips the probe entirely
    out2 = rt.dispatch("f_reject", f, (x,), {})
    assert np.array_equal(np.asarray(out2), x + 3)
    assert rt.stats()["misses"] == 2


# ------------------------------------------------------- fallback ladder


def test_env_mismatch_disables_runtime(tmp_path):
    """An index built in a different environment (kernel edit, jaxlib
    bump, other backend/topology) must disable the WHOLE artifact set and
    fall back to the trace path — never load a stale executable."""
    store = aot.AotStore(str(tmp_path))
    env = aot.env_signature()
    bad = dict(env, kernel_digest="0" * 64)
    store.write_index(bad, [{"row": "x", "sig_key": "k",
                             "artifact": "x.aotx", "family": "serving"}])
    rt = aot.AotRuntime(store, mode="serve")
    assert rt.disabled_reason is not None
    assert "kernel_digest" in rt.disabled_reason

    @jax.jit
    def f(x):
        return x + 1

    x = np.ones((2,), np.float32)
    out = rt.dispatch("f", f, (x,), {})
    assert np.array_equal(np.asarray(out), x + 1)   # jit fallback works


def test_missing_artifact_falls_back_per_bucket(tmp_path):
    """A row whose .aotx payload is unreadable reports ok=False from
    preload and the signature goes on the per-bucket fallback path —
    dispatch still answers via the jit."""
    @jax.jit
    def f(x):
        return x * 2

    x = np.ones((2,), np.float32)
    key = aot.call_signature("f", f, (x,), {})[0]
    store = aot.AotStore(str(tmp_path))
    store.write_index(aot.env_signature(),
                      [{"row": "serving:f@b2", "family": "serving",
                        "program": "f", "sig_key": key,
                        "artifact": "gone.aotx", "pod_bucket": 2}])
    rt = aot.AotRuntime(store, mode="serve")
    assert rt.disabled_reason is None
    report = rt.preload()
    assert len(report) == 1 and not report[0]["ok"]
    out = rt.dispatch("f", f, (x,), {})
    assert np.array_equal(np.asarray(out), x * 2)
    st = rt.stats()
    assert st["misses"] == 1 and st["loads"] == 0


def test_unknown_signature_is_remembered_as_miss(tmp_path):
    store = aot.AotStore(str(tmp_path))
    store.write_index(aot.env_signature(), [])
    rt = aot.AotRuntime(store, mode="serve")

    @jax.jit
    def f(x):
        return x - 1

    x = np.ones((2,), np.float32)
    for _ in range(2):
        rt.dispatch("f", f, (x,), {})
    assert rt.stats()["misses"] == 2


def test_maybe_arm_from_env(tmp_path, monkeypatch):
    """KUBETPU_AOT_DIR arms iff the index exists and matches this env;
    a bad dir must NEVER block serving (returns None, stays disarmed)."""
    monkeypatch.setenv(aot.DIR_ENV, str(tmp_path / "nope"))
    aot.disarm()
    assert aot.maybe_arm_from_env() is None
    store = aot.AotStore(str(tmp_path))
    store.write_index(aot.env_signature(), [])
    monkeypatch.setenv(aot.DIR_ENV, str(tmp_path))
    rt = aot.maybe_arm_from_env()
    try:
        assert rt is not None and rt.mode == "serve"
    finally:
        aot.disarm()


# -------------------------------------------------------- ladder pruning


def test_serving_buckets_and_allows_bucket(tmp_path):
    store = aot.AotStore(str(tmp_path))
    rows = [{"row": "a", "family": "serving", "sig_key": "k1",
             "artifact": "a.aotx", "pod_bucket": 8},
            {"row": "b", "family": "serving", "sig_key": "k2",
             "artifact": "b.aotx", "pod_bucket": 64},
            {"row": "c", "family": "census", "sig_key": "k3",
             "artifact": "c.aotx", "pod_bucket": 128}]
    store.write_index(aot.env_signature(), rows)
    rt = aot.AotRuntime(store, mode="serve")
    assert rt.serving_buckets() == {8, 64}      # census rows don't count
    assert rt.allows_bucket(8) and rt.allows_bucket(64)
    assert not rt.allows_bucket(128)            # pruned rung: skip dry-run
    # empty artifact set = no pruning information: walk the full ladder
    empty = aot.AotStore(str(tmp_path / "empty"))
    empty.write_index(aot.env_signature(), [])
    assert aot.AotRuntime(empty, mode="serve").allows_bucket(128)


def _write_closure(path, keys):
    """A minimal CLOSURE_MANIFEST.json whose combos cover exactly
    ``keys`` (registry entry keys, "program" or "program:tag")."""
    programs = {}
    for k in keys:
        prog = programs.setdefault(k.partition(":")[0], {"combos": {}})
        prog["combos"][k] = {"assignment": {},
                             "coverage": "registry:" + k, "reason": ""}
    path.write_text(json.dumps({"programs": programs}))


def test_prune_drops_unserved_buckets_and_dead_census_rows(tmp_path):
    """tools/kubeaot --prune: serving rows whose pod bucket the flight
    recorder never saw are dead rungs (payload deleted, row dropped);
    census rows whose manifest row is gone (the census drift gate's
    "removed" class) go the same way; and — the proof join — census rows
    whose rung the committed closure no longer proves reachable are dead
    even while their manifest row lingers."""
    from tools.kubeaot.build import prune
    store = aot.AotStore(str(tmp_path))
    rows = []
    for name, fam, bucket, rid in (
            ("s8.aotx", "serving", 8, "serving:g@b8"),
            ("s64.aotx", "serving", 64, "serving:g@b64"),
            ("c1.aotx", "census", 8, "_schedule_gang@n8_b8"),
            ("c2.aotx", "census", 8, "_schedule_gang@n_gone"),
            ("c3.aotx", "census", 8, "_schedule_gang:dead@n8_b8")):
        store.save(name, {}, b"payload", None, None, [0])
        rows.append({"row": rid, "family": fam, "sig_key": name,
                     "artifact": name, "pod_bucket": bucket})
    store.write_index(aot.env_signature(), rows)
    trace_path = tmp_path / "trace.json"
    trace_path.write_text(json.dumps(
        {"cycle_meta": [{"seq": 1, "label": "cycle",
                         "meta": {"pod_bucket": 8}},
                        {"seq": 2, "label": "prewarm", "meta": {}}]}))
    manifest_rows = [{"program": "_schedule_gang", "tag": "",
                      "variant": "n8_b8"},
                     {"program": "_schedule_gang", "tag": "dead",
                      "variant": "n8_b8"}]
    closure_path = tmp_path / "closure.json"
    _write_closure(closure_path, ["_schedule_gang"])   # :dead unproved
    rep = prune(str(tmp_path), trace_path=str(trace_path),
                manifest_rows=manifest_rows,
                closure_path=str(closure_path))
    assert rep["kept"] == 2
    assert sorted(rep["dropped"]) == ["_schedule_gang:dead@n8_b8",
                                      "_schedule_gang@n_gone",
                                      "serving:g@b64"]
    assert rep["unproved"] == ["_schedule_gang:dead@n8_b8"]
    assert not os.path.exists(tmp_path / "s64.aotx")
    assert not os.path.exists(tmp_path / "c3.aotx")
    assert os.path.exists(tmp_path / "s8.aotx")
    kept_rows = {r["row"] for r in store.read_index()["rows"]}
    assert kept_rows == {"serving:g@b8", "_schedule_gang@n8_b8"}


def test_prune_without_closure_skips_proof_join(tmp_path):
    """No committed closure = no proof information: prune must keep
    census rows rather than treat every rung as unreachable."""
    from tools.kubeaot.build import prune
    store = aot.AotStore(str(tmp_path))
    store.save("c1.aotx", {}, b"payload", None, None, [0])
    store.write_index(aot.env_signature(), [
        {"row": "_schedule_gang@n8_b8", "family": "census",
         "sig_key": "c1.aotx", "artifact": "c1.aotx", "pod_bucket": 8}])
    rep = prune(str(tmp_path),
                manifest_rows=[{"program": "_schedule_gang", "tag": "",
                                "variant": "n8_b8"}],
                closure_path=str(tmp_path / "absent.json"))
    assert rep["kept"] == 1 and rep["unproved"] == []


# ------------------------------------------------------------- CI gates


def _write_manifest(path, ids):
    rows = []
    for rid in ids:
        program, _, variant = rid.partition("@")
        program, _, tag = program.partition(":")
        rows.append({"program": program, "tag": tag, "variant": variant})
    path.write_text(json.dumps({"rows": rows}))


def test_check_index_passes_on_matching_keys(tmp_path):
    from tools.kubeaot.build import check_index
    ids = ["_schedule_gang@n8_b8", "_schedule_sequential@n64_b64"]
    man = tmp_path / "manifest.json"
    _write_manifest(man, ids + ["filter_verdicts@n8_b8",     # not seamed
                                "_schedule_gang@n8_b8@mesh"])
    idx = tmp_path / "index.json"
    idx.write_text(json.dumps(
        {"rows": [{"row": rid, "family": "census"} for rid in ids]
         + [{"row": "serving:x@b8", "family": "serving"}]}))
    closure = tmp_path / "closure.json"
    _write_closure(closure, ["_schedule_gang", "_schedule_sequential"])
    assert check_index(str(idx), manifest_path=str(man),
                       closure_path=str(closure)) == []


def test_check_index_fails_both_directions(tmp_path):
    from tools.kubeaot.build import check_index
    man = tmp_path / "manifest.json"
    _write_manifest(man, ["_schedule_gang@n8_b8",
                          "_schedule_gang@n64_b64"])
    idx = tmp_path / "index.json"
    idx.write_text(json.dumps(
        {"rows": [{"row": "_schedule_gang@n8_b8", "family": "census"},
                  {"row": "_schedule_gang@n_stale", "family": "census"}]}))
    failures = check_index(str(idx), manifest_path=str(man),
                           closure_path=str(tmp_path / "absent.json"))
    assert any("manifest row with no artifact: _schedule_gang@n64_b64"
               in f for f in failures)
    assert any("artifact with no manifest row: _schedule_gang@n_stale"
               in f for f in failures)


def test_check_index_flags_prune_closure_disagreement(tmp_path):
    """Both disagreement directions: an artifact rung outside the proved
    closure (should have been pruned), and a closure-reachable rung of an
    AOT program with no artifact (build lags the proof)."""
    from tools.kubeaot.build import check_index
    ids = ["_schedule_gang@n8_b8", "_schedule_gang:bias@n8_b8"]
    man = tmp_path / "manifest.json"
    _write_manifest(man, ids)
    idx = tmp_path / "index.json"
    idx.write_text(json.dumps(
        {"rows": [{"row": rid, "family": "census"} for rid in ids]}))
    closure = tmp_path / "closure.json"
    # :bias artifact is unproved; :hostok is proved but has no artifact
    _write_closure(closure, ["_schedule_gang", "_schedule_gang:hostok",
                             "_apply_cluster_delta:donated"])  # not AOT
    failures = check_index(str(idx), manifest_path=str(man),
                           closure_path=str(closure))
    assert any("outside the proved closure" in f
               and "_schedule_gang:bias" in f for f in failures)
    assert any("no artifact" in f and "_schedule_gang:hostok" in f
               and "closure" in f for f in failures)
    # non-AOT closure programs (delta appliers) never demand artifacts
    assert not any("_apply_cluster_delta" in f for f in failures)


def test_flush_index_replaces_stale_rows(tmp_path):
    """A re-captured variant must REPLACE its previous index row: a
    call-form change (e.g. positional -> keyword host_ok) would otherwise
    leave the dead signature mapping behind, costing a wasted deserialize
    + rejected call at serve, and making rebuilds history-dependent."""
    store = aot.AotStore(str(tmp_path))
    env = aot.env_signature()
    store.write_index(env, [
        {"row": "_p:hostok@n8_b8", "family": "census",
         "sig_key": "stale-positional", "artifact": "old.aotx"},
        {"row": "_p:dead@n8_b8", "family": "census",
         "sig_key": "dead", "artifact": "dead.aotx"},
        {"row": "serving:q@b8/k", "family": "serving",
         "sig_key": "k", "artifact": "s.aotx"}])
    rt = aot.AotRuntime(store, mode="capture", family="census")
    fresh = {"row": "_p:hostok@n8_b8", "family": "census",
             "sig_key": "fresh-keyword", "artifact": "new.aotx"}
    with rt._lock:
        rt._rows.append(fresh)
        rt._rows_by_sig["fresh-keyword"] = fresh
    rt.flush_index(replace_family="census")
    rows = {r["row"]: r for r in store.read_index()["rows"]}
    # re-captured row replaced (ONE entry, the fresh sig), dead census
    # row dropped (census family rebuilt exhaustively), serving row kept
    assert rows["_p:hostok@n8_b8"]["sig_key"] == "fresh-keyword"
    assert "_p:dead@n8_b8" not in rows
    assert "serving:q@b8/k" in rows
    assert len(rows) == 2


def test_committed_index_has_no_duplicate_row_ids():
    """make-aot idempotence: the committed AOT_INDEX.json carries exactly
    one row per row id (stale call-form twins would shadow live ones)."""
    import collections

    from tools.kubeaot.build import INDEX_COMMIT_PATH
    with open(INDEX_COMMIT_PATH) as f:
        rows = json.load(f)["rows"]
    counts = collections.Counter(r["row"] for r in rows)
    dupes = {k: v for k, v in counts.items() if v > 1}
    assert not dupes, "duplicate index rows: %s" % dupes


def test_check_index_unreadable_index(tmp_path):
    from tools.kubeaot.build import check_index
    failures = check_index(str(tmp_path / "absent.json"))
    assert failures and "unreadable" in failures[0]


def test_committed_index_matches_committed_manifest():
    """The in-tree gate itself: tools/kubeaot/AOT_INDEX.json and
    COMPILE_MANIFEST.json agree on census-family row keys (what
    ci_lint.sh runs)."""
    from tools.kubeaot.build import check_index
    assert check_index() == []


def test_cli_check_mode(tmp_path):
    from tools.kubeaot.__main__ import main
    ids = ["_schedule_gang@n8_b8"]
    man = tmp_path / "manifest.json"
    _write_manifest(man, ids)
    idx = tmp_path / "index.json"
    idx.write_text(json.dumps(
        {"rows": [{"row": rid, "family": "census"} for rid in ids]}))
    closure = tmp_path / "closure.json"
    _write_closure(closure, ["_schedule_gang"])
    import tools.kubecensus.manifest as m
    old = m.MANIFEST_PATH
    m.MANIFEST_PATH = str(man)
    try:
        assert main(["--check", "--index", str(idx),
                     "--closure", str(closure), "--json"]) == 0
        idx.write_text(json.dumps({"rows": []}))
        assert main(["--check", "--index", str(idx),
                     "--closure", str(closure), "--json"]) == 1
    finally:
        m.MANIFEST_PATH = old


# --------------------------------------------------- restart end-to-end


@pytest.mark.slow
def test_build_shape_capture_serves_restart(tmp_path):
    """The tentpole end-to-end: a deploy-shaped capture (build_shape over
    the SHARED hollow.restart_world/restart_wave builders) followed by a
    simulated process restart (clear_caches + serve-armed Scheduler) —
    prewarm deserialize-loads the artifacts, the first cycle's dispatches
    HIT, and the wave schedules identically to the capture drain."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    from tools.kubeaot.build import build_shape

    aot_dir = str(tmp_path / "aot")
    rep = build_shape(aot_dir, 16, 16, ladder=0, existing_per_node=1)
    assert rep["rows"] > 0 and rep["stats"]["misses"] == 0

    jax.clear_caches()
    rt = aot.arm(aot.serve_runtime(aot_dir))
    try:
        assert rt.disabled_reason is None
        store = hollow.restart_world(16, existing_per_node=1)
        sched = Scheduler(store, config=KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile()], batch_size=16,
            mode="gang", chain_cycles=True), async_binding=False)
        assert sched.prewarm()            # the aot preload path
        assert rt.stats()["loads"] == rep["rows"]
        for p in hollow.restart_wave(16):
            store.add(p)
        out = sched.schedule_pending(timeout=1.0)
        st = rt.stats()
        assert st["hits"] > 0, "first cycle did not hit the artifact set"
        assert st["misses"] == 0, \
            "capture missed a serving call form: %s" % st
        assert sum(1 for o in out if o.node) == rep["scheduled"]
        sched.close()
    finally:
        aot.disarm()
