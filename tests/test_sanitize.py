"""Runtime-sanitizer harness tests (kubetpu/utils/sanitize.py).

The headline test runs full scheduling cycles — store -> queue -> device
program -> bind — under the sanitizer (jax_debug_nans,
rank_promotion="raise", compile-count watchdog) in BOTH execution modes
and asserts:

  * no rank-promotion errors and no NaNs anywhere in the traced programs
    (the cluster tensors are NaN-free by contract: state/tensors.py uses
    +inf for absent numeric labels precisely so this check has teeth);
  * ZERO recompiles — a second same-bucket cycle must hit every compiled
    program's jit cache (the pow2-bucketing contract, utils/intern.py).
"""

import logging

import numpy as np
import pytest

from kubetpu.utils import sanitize
from kubetpu.utils.sanitize import (CompileWatchdog, sanitized,
                                    sanitize_enabled)


def make_sched(mode="sequential", **cfg_kw):
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    store = ClusterStore()
    for n in hollow.make_nodes(4, zones=2):
        store.add(n)
    cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                     mode=mode, prewarm=False, **cfg_kw)
    return store, Scheduler(store, config=cfg, async_binding=False)


def run_cycles(store, sched, waves=2, pods_per_wave=6):
    from kubetpu.harness import hollow
    outcomes = []
    for w in range(waves):
        for p in hollow.make_pods(pods_per_wave, prefix=f"wave{w}-"):
            store.add(p)
        outcomes.extend(sched.schedule_pending(timeout=0.0))
    return outcomes


@pytest.mark.parametrize("mode", ["sequential", "gang"])
def test_scheduling_cycle_under_sanitizer(mode, monkeypatch):
    """Satellite acceptance: a scheduling cycle under KUBETPU_SANITIZE=1
    runs with zero recompiles, no rank-promotion errors, no NaNs."""
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    assert sanitize_enabled()
    owned = sanitize.current_watchdog() is None
    # earlier tests in the same process may already have compiled this
    # scenario's exact (program, shape) set — start cold so the
    # compile_count() > 0 assertion below measures THIS test's work
    import jax
    jax.clear_caches()
    with sanitized() as wd:
        store, sched = make_sched(mode=mode)
        outcomes = run_cycles(store, sched, waves=2)
        assert len(outcomes) == 12
        assert all(o.err is None and o.node for o in outcomes), \
            [(o.node, o.err) for o in outcomes]
        # same pod-count bucket both waves: every program compiled at most
        # once per (program, shape) key
        wd.assert_no_recompilation()
        assert wd.compile_count() > 0  # the watchdog actually observed work
        assert not wd.donation_mismatches
    # config restored after the context exits — unless the sanitizer was
    # already armed process-wide (KUBETPU_SANITIZE=1 at import), in which
    # case the scoped context must NOT tear it down
    import jax
    if owned:
        assert jax.config.jax_debug_nans is False
        assert jax.config.jax_numpy_rank_promotion == "allow"
    else:
        assert jax.config.jax_debug_nans is True
        assert sanitize.current_watchdog() is not None


def test_chained_gang_cycles_under_sanitizer(monkeypatch):
    """Cycle chaining materializes the next cluster on device; under the
    sanitizer the chained path must stay NaN-free and rank-exact too."""
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    with sanitized() as wd:
        store, sched = make_sched(mode="gang", chain_cycles=True)
        outcomes = run_cycles(store, sched, waves=2)
        assert all(o.err is None and o.node for o in outcomes)
        wd.assert_no_recompilation()


def test_watchdog_counts_and_flags_recompiles():
    wd = CompileWatchdog()

    def rec(msg):
        return logging.LogRecord("jax._src.interpreters.pxla",
                                 logging.DEBUG, __file__, 1, msg, (), None)

    msg_a = ("Compiling jit(prog) with global shapes and types "
             "(ShapedArray(float32[8,4]),). Argument mapping: "
             "(UnspecifiedValue,).")
    msg_b = ("Compiling jit(prog) with global shapes and types "
             "(ShapedArray(float32[16,4]),). Argument mapping: "
             "(UnspecifiedValue,).")
    wd.emit(rec(msg_a))
    wd.emit(rec(msg_b))
    wd.assert_no_recompilation()  # two SHAPES, one compile each: fine
    assert {name for name, _ in wd.counts} == {"prog"}  # jit() stripped
    wd.emit(rec(msg_a))           # same program+shape again: cache defeated
    assert wd.recompiled()
    with pytest.raises(AssertionError, match="jit cache defeated"):
        wd.assert_no_recompilation()
    wd.reset()
    assert wd.compile_count() == 0


def test_watchdog_counts_a_fresh_jit_once_per_shape():
    """Against the INSTALLED jax's real log record (not a hand-written
    one): a fresh jit compiles exactly once per shape, a repeat call is a
    jit-cache hit and counts nothing.  A watchdog whose pattern drifted
    from the record sees zero compiles and every zero-recompile gate
    built on it passes vacuously."""
    import jax
    wd = sanitize.install_compile_watchdog()
    try:
        @jax.jit
        def watchdog_probe(x):
            return x * 2 + 1

        def probe_compiles():
            return sum(c for (name, _), c in wd.counts.items()
                       if name == "watchdog_probe")

        np.asarray(watchdog_probe(np.ones((3, 5), np.float32)))
        assert probe_compiles() == 1
        np.asarray(watchdog_probe(np.ones((3, 5), np.float32)))
        assert probe_compiles() == 1
        np.asarray(watchdog_probe(np.ones((7, 5), np.float32)))
        assert probe_compiles() == 2
        wd.assert_no_recompilation()
    finally:
        sanitize.uninstall_compile_watchdog(wd)


def test_watchdog_records_donation_mismatch():
    # logging path (some jax versions route donation complaints here)
    wd = CompileWatchdog()
    wd.emit(logging.LogRecord(
        "jax._src.interpreters.pxla", logging.WARNING, __file__, 1,
        "Some donated buffers were not usable: f32[8]", (), None))
    assert wd.donation_mismatches


def test_donation_warning_captured_through_warnings_hook(monkeypatch):
    """jax emits 'Some donated buffers were not usable' via warnings.warn
    (jax/_src/interpreters/mlir.py); the sanitizer hooks showwarning so
    the watchdog sees it — and restores the hook on exit."""
    import warnings
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    # a hook installed before pytest's own warning capture would be
    # shadowed by it — force an owned scope so the hook lands inside
    was_armed = sanitize.current_watchdog() is not None
    if was_armed:
        sanitize.disable_sanitizer()
    try:
        before = warnings.showwarning
        with sanitized() as wd:
            with warnings.catch_warnings():
                warnings.simplefilter("always")
                warnings.warn(
                    "Some donated buffers were not usable: f32[8]{0}")
            assert wd.donation_mismatches
        assert warnings.showwarning is before
    finally:
        if was_armed:
            sanitize.enable_sanitizer()


def test_sanitizer_catches_rank_promotion(monkeypatch):
    """The harness actually rejects implicit rank promotion (this exact
    class of bug was live in fit_filter before the sanitizer landed)."""
    import jax.numpy as jnp
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    with sanitized():
        with pytest.raises(ValueError, match="rank_promotion|broadcast"):
            _ = jnp.ones((4, 8, 12), bool) | jnp.zeros((12,), bool)  # noqa


def test_sanitizer_catches_nan(monkeypatch):
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    with sanitized():
        with pytest.raises(FloatingPointError):
            jax.jit(lambda x: jnp.log(x))(jnp.zeros((4,)) - 1.0).block_until_ready()


def test_cluster_tensors_are_nan_free():
    """The +inf numeric-label sentinel contract: a tensorized cluster must
    contain no NaNs anywhere, or debug_nans false-positives on every
    program that returns cluster arrays (e.g. materialize_assigned)."""
    import jax
    from kubetpu.api import types as api
    from kubetpu.framework.types import NodeInfo
    from kubetpu.state.tensors import SnapshotBuilder
    node = api.Node(metadata=api.ObjectMeta(
        name="n0", labels={api.LABEL_HOSTNAME: "n0", "gpus": "4",
                           "tier": "gold"}),
        status=api.NodeStatus(allocatable={"cpu": "4", "memory": "8Gi",
                                           "pods": "110"}))
    host = SnapshotBuilder().build([NodeInfo(node)])
    for name, arr in host.arrays.items():
        if isinstance(arr, np.ndarray) and arr.dtype.kind == "f":
            assert not np.isnan(arr).any(), f"NaN in cluster tensor {name}"


def test_numeric_label_selector_semantics_with_inf_sentinel():
    """Gt/Lt selector matching must be unchanged by the NaN->+inf sentinel
    swap: numeric labels compare, absent/non-numeric never match."""
    from kubetpu.api import types as api
    from tests.harness import run_cluster

    def node(name, labels):
        lab = {api.LABEL_HOSTNAME: name}
        lab.update(labels)
        return api.Node(
            metadata=api.ObjectMeta(name=name, labels=lab),
            status=api.NodeStatus(allocatable={"cpu": "4", "memory": "8Gi",
                                               "pods": "110"}))

    nodes = [node("big", {"gpus": "8"}), node("small", {"gpus": "2"}),
             node("weird", {"gpus": "many"}), node("none", {})]
    pod = api.Pod(
        metadata=api.ObjectMeta(name="p", namespace="default"),
        spec=api.PodSpec(containers=[api.Container(name="c", image="")]))
    pod.spec.affinity = api.Affinity(node_affinity=api.NodeAffinity(
        required_during_scheduling_ignored_during_execution=api.NodeSelector(
            node_selector_terms=[api.NodeSelectorTerm(
                match_expressions=[api.NodeSelectorRequirement(
                    key="gpus", operator="Gt", values=["4"])])])))
    res = run_cluster(nodes, pending=[pod])
    by = dict(zip(res.node_names, res.feasible[0]))
    assert bool(by["big"]) is True        # 8 > 4
    assert bool(by["small"]) is False     # 2 > 4 fails
    assert bool(by["weird"]) is False     # non-numeric never matches
    assert bool(by["none"]) is False      # absent never matches


def test_sanitized_joins_env_armed_sanitizer(monkeypatch):
    """A sanitizer armed process-wide (KUBETPU_SANITIZE=1 at import) must
    survive scoped sanitized() blocks — the context only tears down what
    it enabled."""
    monkeypatch.setenv(sanitize.ENV_FLAG, "1")
    wd = sanitize.maybe_enable_from_env()
    assert wd is not None
    try:
        wd.counts[("stale", "(f32[8],)")] = 2
        with sanitized() as wd2:
            assert wd2 is wd
            # joining resets counts so this scope judges only its own work
            assert wd2.compile_count() == 0
        assert sanitize.current_watchdog() is wd  # still armed
    finally:
        sanitize.disable_sanitizer()
    assert sanitize.current_watchdog() is None


def test_maybe_enable_from_env_off_by_default(monkeypatch):
    monkeypatch.delenv(sanitize.ENV_FLAG, raising=False)
    assert sanitize.maybe_enable_from_env() is None
    assert sanitize.current_watchdog() is None


def test_watchdog_uninstall_restores_and_respects_active_sanitizer():
    """The pxla-logger arming is refcounted across the standalone compile
    watchdog and the full sanitizer: uninstalling the standalone watchdog
    while the sanitizer is active must leave the logger armed (DEBUG,
    records flowing to the sanitizer's watchdog), and the ORIGINAL
    level/propagate come back only when the last handler detaches."""
    logger = logging.getLogger(sanitize._PXLA_LOGGER)
    prev_level, prev_prop = logger.level, logger.propagate
    logger.setLevel(logging.WARNING)
    logger.propagate = True
    try:
        wd = sanitize.install_compile_watchdog()
        assert logger.level == logging.DEBUG
        swd = sanitize.enable_sanitizer()
        assert swd is not wd
        sanitize.uninstall_compile_watchdog(wd)
        # sanitizer still armed: logger must stay open for ITS watchdog
        assert logger.level == logging.DEBUG
        logger.handle(logging.LogRecord(
            sanitize._PXLA_LOGGER, logging.DEBUG, __file__, 1,
            "Compiling jit(prog) with global shapes and types "
            "(ShapedArray(float32[8,4]),). Argument mapping: "
            "(UnspecifiedValue,).",
            (), None))
        assert swd.compile_count() == 1
        sanitize.disable_sanitizer()
        # last handler gone: the ORIGINAL state (not a stale snapshot)
        assert logger.level == logging.WARNING
        assert logger.propagate is True
        # plain install/uninstall pair restores too
        wd2 = sanitize.install_compile_watchdog()
        assert logger.level == logging.DEBUG
        sanitize.uninstall_compile_watchdog(wd2)
        assert logger.level == logging.WARNING
    finally:
        sanitize.disable_sanitizer()
        logger.setLevel(prev_level)
        logger.propagate = prev_prop
