"""kubeexact self-tests: every prover rule fires on a known-bad snippet
and stays quiet on the matching known-good one, the manifest serializes
byte-identically, the drift gate sees both directions, exemption
staleness is audited, and the committed EXACT_MANIFEST.json passes the
pure-JSON --check gate."""

import dataclasses
import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from tools.kubeexact.driver import (ExactResult, ProofResult,  # noqa: E402
                                    prove_callable, prove_entry, run_exact)
from tools.kubeexact.manifest import (build_manifest,  # noqa: E402
                                      check_manifest, diff_manifest,
                                      load_manifest, write_manifest)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ENV = {"B": 4096.0, "N": 16384.0, "P": 131072.0, "MESH:i": 4.0}


def _mesh():
    # two devices: a singleton mesh lets jax elide the psum entirely,
    # which would hide the reduction from the prover
    return Mesh(np.array(jax.devices()[:2]), ("i",))


def _census(tmp_path, *keys):
    """A minimal COMPILE_MANIFEST twin licensing ``keys`` (the census
    join half of check_manifest)."""
    rows = []
    for k in keys:
        prog, _, tag = k.partition(":")
        rows.append({"program": prog, "tag": tag})
    p = tmp_path / "census.json"
    p.write_text(json.dumps({"rows": rows}))
    return str(p)


def rule_ids(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# bad snippets: one per prover rule


def test_noninteger_float_psum_fires():
    mesh = _mesh()

    def bad(x):
        return jax.shard_map(lambda t: jax.lax.psum(t * 0.5, "i"),
                         mesh=mesh, in_specs=P("i"), out_specs=P(),
                         check_vma=False)(x)

    proofs, findings = prove_callable(
        "bad:psum", bad, (np.zeros((4, 8), np.float32),),
        sizes={"B": 4, "N": 8}, env=_ENV)
    assert "exact/nonexact-psum" in rule_ids(findings)
    assert any(p["status"] == "violation" for p in proofs)


def test_out_of_range_integer_sum_fires():
    mesh = _mesh()

    def bad(x):
        # integer-valued (floor of a clip) but each element can reach
        # 4096: summed over the N axis the bound is N*4096 = 2**26 at
        # the north-star environment — past the exact f32 integer range
        y = jnp.floor(jnp.clip(x, 0.0, 4096.0))
        s = jnp.sum(y, axis=-1)
        return jax.shard_map(lambda t: jax.lax.psum(t, "i"),
                         mesh=mesh, in_specs=P("i"), out_specs=P(),
                         check_vma=False)(s)

    proofs, findings = prove_callable(
        "bad:overflow", bad, (np.zeros((4, 8), np.float32),),
        sizes={"B": 4, "N": 8}, env=dict(_ENV, **{"MESH:i": 1.0}))
    assert "exact/sum-overflow" in rule_ids(findings)
    over = [p for p in proofs if p["status"] == "violation"]
    assert over and over[0]["rule"] == "exact/sum-overflow"
    assert "bound" in over[0]


def test_shardmap_row_gather_fires():
    mesh = _mesh()

    def bad(x):
        return jax.shard_map(
            lambda t: jax.lax.all_gather(t, "i", tiled=True),
            mesh=mesh, in_specs=P("i"), out_specs=P("i"),
            check_vma=False)(x)

    _, findings = prove_callable(
        "bad:gather", bad, (np.zeros((4, 8), np.float32),),
        sizes={"B": 4, "N": 8}, env=_ENV)
    assert "exact/shardmap-row-gather" in rule_ids(findings)


def test_raw_tie_argmax_fires_and_gumbel_is_clean():
    def bad(x):
        return jnp.argmax(x, axis=-1)

    _, findings = prove_callable(
        "bad:argmax", bad, (np.zeros((4, 8), np.float32),), env=_ENV)
    assert "exact/raw-tie-argmax" in rule_ids(findings)

    def good(x):
        g = jax.random.gumbel(jax.random.PRNGKey(0), x.shape, jnp.float32)
        return jnp.argmax(jnp.where(x > 0, g, -jnp.inf), axis=-1)

    _, findings = prove_callable(
        "good:argmax", good, (np.zeros((4, 8), np.float32),), env=_ENV)
    assert "exact/raw-tie-argmax" not in rule_ids(findings)


def test_clean_snippet_is_empty():
    mesh = _mesh()

    def good(x):
        counts = jnp.sum(jnp.where(x > 0, 1.0, 0.0), axis=-1)
        return jax.shard_map(lambda t: jax.lax.psum(t, "i"),
                         mesh=mesh, in_specs=P("i"), out_specs=P(),
                         check_vma=False)(counts)

    proofs, findings = prove_callable(
        "good:counts", good, (np.zeros((4, 8), np.float32),),
        sizes={"B": 4, "N": 8}, env=_ENV)
    assert findings == []
    assert proofs and all(p["status"] == "exact" for p in proofs)


# ---------------------------------------------------------------------------
# manifest: deterministic serialization + two-directional drift


def _tiny_result():
    pr = ProofResult(
        program="prog:variant",
        proofs=[{"op": "psum", "kind": "sum", "axes": ["pods"],
                 "dtype": "float32", "shape": [8], "int_valued": True,
                 "status": "exact", "bound": "max(0, N)",
                 "bound_northstar": 16384.0, "margin": 1024.0,
                 "why": "integer-valued sum"}],
        findings=[], suppressed=[],
        surface={"n8_b8": [{"op": "psum", "kind": "sum", "axes": ["pods"],
                            "dtype": "float32", "shape": [8],
                            "bytes": 32}]},
        facts=(("zone_hot", "onehot_rows"),))
    return ExactResult(results=[pr],
                       headroom={"floor": 4.0, "min_margin": 1024.0,
                                 "dominating": "prog:variant",
                                 "int_exact_limit": float(2 ** 24)},
                       findings=[], suppressed=[])


def test_manifest_regeneration_is_byte_identical(tmp_path):
    doc = build_manifest(_tiny_result())
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    write_manifest(doc, str(p1))
    write_manifest(build_manifest(_tiny_result()), str(p2))
    assert p1.read_bytes() == p2.read_bytes()
    assert p1.read_bytes().endswith(b"\n")
    assert load_manifest(str(p1)) == doc


def test_drift_gate_both_directions():
    cur = build_manifest(_tiny_result())
    com = json.loads(json.dumps(cur))
    assert diff_manifest(cur, com) == {"added": [], "removed": [],
                                       "changed": []}
    # added: proved program the committed file lacks
    grown = json.loads(json.dumps(cur))
    grown["programs"]["new:prog"] = grown["programs"]["prog:variant"]
    assert diff_manifest(grown, com)["added"] == ["new:prog"]
    # removed: committed program no trace reproduces
    assert diff_manifest(com, grown)["removed"] == ["new:prog"]
    # changed: same key, different proof rows
    mut = json.loads(json.dumps(cur))
    mut["programs"]["prog:variant"]["proofs"][0]["margin"] = 2.0
    assert diff_manifest(mut, com)["changed"] == ["prog:variant (proofs)"]
    # the committed environment itself is watched
    env = json.loads(json.dumps(cur))
    env["northstar_env"]["B"] = 8192.0
    assert "<northstar_env>" in diff_manifest(env, com)["changed"]
    # no manifest at all
    assert diff_manifest(cur, None)["missing_manifest"]


def test_check_manifest_pure_json(tmp_path):
    census = _census(tmp_path, "prog:variant")
    doc = build_manifest(_tiny_result())
    assert check_manifest(doc, census_path=census) == []
    # margin below the committed floor fails
    low = json.loads(json.dumps(doc))
    low["programs"]["prog:variant"]["proofs"][0]["margin"] = 2.0
    assert any("floor" in f for f in check_manifest(low, census_path=census))
    # a violation status fails
    bad = json.loads(json.dumps(doc))
    bad["programs"]["prog:variant"]["proofs"][0]["status"] = "violation"
    assert any("not exact/exempt" in f
               for f in check_manifest(bad, census_path=census))
    # env drift fails
    env = json.loads(json.dumps(doc))
    env["northstar_env"] = dict(env["northstar_env"], B=1.0)
    assert any("northstar_env" in f
               for f in check_manifest(env, census_path=census))
    assert check_manifest(None)


def test_check_census_join_flags_unlicensed_programs(tmp_path):
    census = _census(tmp_path, "prog:variant")
    doc = build_manifest(_tiny_result())
    doc["programs"]["ghost:prog"] = doc["programs"]["prog:variant"]
    fails = check_manifest(doc, census_path=census)
    assert any("ghost:prog" in f and "unlicensed" in f for f in fails)


# ---------------------------------------------------------------------------
# exemptions: audited, stale ones flagged


def test_stale_exemption_fires(monkeypatch):
    # the shard_map entries build a (1, 1) mesh over the whole backend
    # (the gate's environment has one CPU device); the test session has
    # eight virtual ones, so hand the builder the first
    from kubetpu.parallel import mesh as pmesh
    from tools.kubecensus.registry import ENTRIES
    make_mesh = pmesh.make_mesh
    monkeypatch.setattr(
        pmesh, "make_mesh",
        lambda shape=None, devices=None: make_mesh(
            shape, devices=jax.devices()[:1]))
    entry = next(e for e in ENTRIES
                 if e.exact and e.key == "_shardmap_gang:tiled")
    stale = dataclasses.replace(
        entry, exact_exempt=entry.exact_exempt
        + (("exact/raw-collective-reduce", "obsolete"),))
    res = prove_entry(stale)
    assert "exact/unused-exemption" in rule_ids(res.findings)


# ---------------------------------------------------------------------------
# the committed tree: gate green end to end


def test_committed_manifest_passes_check():
    doc = load_manifest()
    assert doc is not None, "EXACT_MANIFEST.json missing — run --write"
    assert check_manifest(doc) == []


@pytest.mark.slow
def test_intree_programs_prove_exact():
    # subprocess with the forced-8-device flag stripped: the shard_map
    # registry entries build (1, 1) meshes, exactly like the ci_lint
    # gate environment
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f)
    proc = subprocess.run(
        [sys.executable, "-m", "tools.kubeexact", "--json"],
        capture_output=True, text=True, cwd=REPO, env=env)
    report = json.loads(proc.stdout)
    assert proc.returncode == 0, report
    assert report["clean"] and not report["findings"]
