"""Compile-surface census (tools/kubecensus).

Every jaxpr-level rule fires on a bad snippet; manifest generation is
deterministic and idempotent; the drift gate fails on both an added and a
removed variant; the runtime compile-event matcher classifies exact /
structural / outside / auxiliary events; and a FAST subset of the real
registry reproduces its committed COMPILE_MANIFEST.json rows bit-for-bit
(the full-tree gate runs in tools/ci_lint.sh via
``python -m tools.kubecensus --check``)."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tools.kubecensus import (ENTRIES, DEFAULT_LADDER, audit_callable,
                              audit_entry, diff_manifest, load_manifest)
from tools.kubecensus.census import trace_variant
from tools.kubecensus.discover import unregistered_roots
from tools.kubecensus.registry import registered_qualnames


def _rules(findings):
    return {f.rule for f in findings}


# ------------------------------------------------- rule firing bad snippets


def test_donation_unconsumed_fires():
    # output dtype differs from the donated arg: XLA cannot alias it
    fn = jax.jit(lambda x, y: (x + y).astype(jnp.int32),
                 donate_argnums=(0,))
    s = np.zeros((8,), np.float32)
    fs = audit_callable("bad_donation", fn, (s, s), donate_argnums=(0,))
    assert "census/donation-unconsumed" in _rules(fs)


def test_donation_consumed_is_clean():
    fn = jax.jit(lambda x, y: x + y, donate_argnums=(0,))
    s = np.zeros((8,), np.float32)
    fs = audit_callable("good_donation", fn, (s, s), donate_argnums=(0,))
    assert "census/donation-unconsumed" not in _rules(fs)


def test_f64_promotion_fires():
    scale = np.float64(2.0)   # committed f64 operand, silently truncated

    def bad(x):
        return x * scale
    fs = audit_callable("bad_f64", bad, (np.zeros((4,), np.float32),))
    assert "census/f64-promotion" in _rules(fs)


def test_weak_python_floats_do_not_fire_f64():
    def ok(x):
        return x * 2.0 + 0.5
    fs = audit_callable("ok_weak", ok, (np.zeros((4,), np.float32),))
    assert "census/f64-promotion" not in _rules(fs)


def test_constant_capture_fires():
    big = np.zeros((1024,), np.float32)

    def bad(x):
        # the whole array rides into the jaxpr as a closed-over constant
        return x * jnp.sum(jnp.asarray(big))
    fs = audit_callable("bad_const", bad, (np.zeros((4,), np.float32),),
                       const_threshold=1024)
    assert "census/constant-capture" in _rules(fs)
    # default threshold leaves the same 4KiB constant alone
    fs = audit_callable("ok_const", bad, (np.zeros((4,), np.float32),))
    assert "census/constant-capture" not in _rules(fs)


def test_host_callback_fires():
    from jax.experimental import io_callback

    def bad(x):
        return io_callback(lambda a: np.asarray(a),
                           jax.ShapeDtypeStruct(x.shape, x.dtype), x) * 2
    fs = audit_callable("bad_cb", bad, (np.zeros((4,), np.float32),))
    assert "census/host-callback" in _rules(fs)


def test_host_callback_seen_through_jit_wrapper():
    from jax.experimental import io_callback

    @jax.jit
    def bad(x):
        return io_callback(lambda a: np.asarray(a),
                           jax.ShapeDtypeStruct(x.shape, x.dtype), x) * 2
    fs = audit_callable("bad_cb_jit", bad, (np.zeros((4,), np.float32),))
    assert "census/host-callback" in _rules(fs)


def test_rank_promotion_fires():
    def bad(x, y):
        return x + y   # [4, 8] + [8]: implicit rank promotion
    fs = audit_callable("bad_rank", bad,
                        (np.zeros((4, 8), np.float32),
                         np.zeros((8,), np.float32)))
    assert "census/rank-promotion" in _rules(fs)


def test_clean_snippet_has_no_findings():
    def ok(x, y):
        return x @ y
    fs = audit_callable("ok", ok, (np.zeros((4, 8), np.float32),
                                   np.zeros((8, 2), np.float32)))
    assert fs == []


# -------------------------------------------------- registry and discovery


def test_registry_covers_every_discovered_jit_root():
    assert unregistered_roots(registered_qualnames()) == []


def test_unregistered_root_finding_fires():
    quals = registered_qualnames()
    victim = "kubetpu.models.programs:filter_and_score"
    fs = unregistered_roots(quals - {victim})
    assert [f.program for f in fs] == [victim]
    assert all(f.rule == "census/unregistered-root" for f in fs)


def test_discovery_resolves_attribute_call_targets(tmp_path):
    """`jax.jit(other_module.f)` — the jitted def living in ANOTHER
    module, reached by attribute — must still be discovered, or a root
    added in that style would silently escape the totality gate."""
    from tools.kubecensus.discover import discover_jit_roots
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    (pkg / "kern.py").write_text("def helper(x):\n    return x\n")
    (pkg / "roots.py").write_text(
        "import jax\nfrom pkg import kern\n"
        "fast = jax.jit(kern.helper)\n")
    roots = discover_jit_roots(paths=("pkg",), root=str(tmp_path))
    assert "pkg.kern:helper" in roots


def test_donated_delta_exemption_is_audited_and_applied():
    e, = [x for x in ENTRIES if x.key == "_apply_cluster_delta:donated"]
    fs = audit_entry(e)
    sup = [f for f in fs if f.suppressed]
    assert sup and all(f.reason for f in sup), \
        "the partial-donation finding must be suppressed WITH a reason"
    assert not [f for f in fs if not f.suppressed]


# ----------------------------------------------- determinism + drift gate


def _fast_entries():
    fast = ("_densify_ids:kv", "whatif_wave", "nominated_fit_mask",
            "filter_and_score")
    return [e for e in ENTRIES if e.key in fast]


def test_trace_is_deterministic_in_process():
    e = _fast_entries()[0]
    r1 = trace_variant(e, DEFAULT_LADDER[0]).row
    r2 = trace_variant(e, DEFAULT_LADDER[0]).row
    assert r1 == r2


def test_committed_manifest_reproduces_for_fast_subset():
    """Bit-for-bit idempotence against the COMMITTED manifest for a fast
    entry subset — the census regenerated over an unchanged tree must
    reproduce its committed rows exactly (the full-tree check is the
    ci_lint.sh drift gate)."""
    committed = load_manifest()
    assert committed, "COMPILE_MANIFEST.json must be committed"
    by_id = {(r["program"], r["tag"], r["variant"]): r for r in committed}
    for e in _fast_entries():
        for rung in e.ladder:
            row = trace_variant(e, rung).row
            key = (row["program"], row["tag"], row["variant"])
            assert key in by_id, f"{key} missing from committed manifest"
            assert row == by_id[key], f"{key} drifted from committed row"


def test_drift_gate_fails_on_added_and_removed_variant():
    committed = load_manifest()
    assert committed
    # unchanged -> clean
    d = diff_manifest(list(committed), committed)
    assert not d["added"] and not d["removed"] and not d["changed"]
    # a NEW traced variant the manifest lacks -> added
    extra = dict(committed[0])
    extra["variant"] = "n4096_b4096"
    d = diff_manifest(list(committed) + [extra], committed)
    assert d["added"] and not d["removed"]
    # a committed row no trace reproduces (dead ladder bucket) -> removed
    d = diff_manifest(list(committed[1:]), committed)
    assert d["removed"] and not d["added"]
    # same id, different jaxpr -> changed
    mut = [dict(r) for r in committed]
    mut[0]["lowering_sha256"] = "0" * 64
    d = diff_manifest(mut, committed)
    assert d["changed"]


def test_real_dispatch_matches_committed_manifest():
    """Close the loop in-process: a REAL dispatch of a kernel root at a
    census rung produces a compile event whose signature IS a committed
    row's ``compiled_in_avals`` (a fresh jit cache is guaranteed by
    using a shape no other test dispatches)."""
    import re

    from kubetpu.utils.sanitize import (install_compile_watchdog,
                                        uninstall_compile_watchdog)
    from tools.kubecensus.registry import build_world

    rows = load_manifest()
    assert rows
    wd = install_compile_watchdog()
    try:
        w = build_world(DEFAULT_LADDER[0])
        from kubetpu.models import programs
        np.asarray(programs.filter_verdicts(w.cluster, w.batch, w.cfg)[0])
        seen = [re.findall(r"ShapedArray\(([^()]*)\)", sig)
                for name, sig in wd.counts if name == "filter_verdicts"]
        assert seen
        committed = [r["compiled_in_avals"] for r in rows
                     if r["program"] == "filter_verdicts"]
        for avals in seen:
            assert avals in committed, avals
    finally:
        uninstall_compile_watchdog(wd)
