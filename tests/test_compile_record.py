"""The compile watchdog's RECORD of a compile (kubetpu/utils/sanitize.py,
PR 26): what was compiled, how long it took, whether it was a compile or a
load from the persistent cache, and in which argument and dimension the
shape signature differs from the nearest one the program was compiled for
before -- on the watchdog's ``records`` and, inside a scheduling cycle, as
the flight event ``xla-compile``; plus the pins on the served programs'
names that the trace readers match."""
import pytest

from kubetpu.utils import sanitize
from kubetpu.utils import trace as utrace
from kubetpu.utils.sanitize import parse_signature, signature_differs

SIG = ("(ShapedArray(float32[5000,12]), ShapedArray(bool[5000]), "
       "ShapedArray(int32[]), ShapedArray(int32[2048,8]))")


@pytest.mark.parametrize("new, want", [
    # one dimension of one argument: the bucket edge that was crossed
    (SIG.replace("int32[2048,8]", "int32[4096,8]"),
     ["arg 3 dim 0: 2048 -> 4096"]),
    # two arguments at once
    # a run of arguments that moved alike is said once
    (SIG.replace("5000", "8192"), ["args 0-1 dim 0: 5000 -> 8192"]),
    (SIG.replace("5000", "8192").replace("2048", "4096"),
     ["args 0-1 dim 0: 5000 -> 8192", "arg 3 dim 0: 2048 -> 4096"]),
    (SIG.replace("bool[5000]", "int8[5000]"), ["arg 1 dtype: bool -> int8"]),
    (SIG.replace("int32[]", "int32[1]"), ["arg 2 rank: 0 -> 1"]),
    (SIG.replace(", ShapedArray(int32[])", ""), ["args: 4 -> 3"]),
    (SIG, []),                       # seen before: a recompile, no diff
])
def test_differs_names_the_argument_and_the_dimension(new, want):
    assert signature_differs([parse_signature(SIG)],
                             parse_signature(new)) == want


def test_differs_is_taken_against_the_nearest_signature_seen():
    seen = [parse_signature(SIG.replace("2048", "512")
                            .replace("5000", "64")),
            parse_signature(SIG)]
    new = parse_signature(SIG.replace("int32[2048,8]", "int32[2048,16]"))
    assert signature_differs(seen, new) == ["arg 3 dim 1: 8 -> 16"]
    assert signature_differs([], new) == []
    assert parse_signature(SIG) == [
        ("float32", (5000, 12)), ("bool", (5000,)), ("int32", ()),
        ("int32", (2048, 8))]


@pytest.fixture
def watchdog():
    wd = sanitize.install_compile_watchdog()
    wd.reset()
    try:
        yield wd
    finally:
        sanitize.uninstall_compile_watchdog(wd)


def test_a_compile_is_recorded_with_its_kind_seconds_and_diff(watchdog):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def compile_record_probe(x, y):
        return (x @ x).sum() + y.sum()
    t0 = utrace.wallclock()
    compile_record_probe(jnp.ones((8, 8)), jnp.ones((4,)))
    compile_record_probe(jnp.ones((8, 8)), jnp.ones((16,)))
    mine = [r for r in watchdog.records
            if r["program"] == "compile_record_probe"]
    assert len(mine) == 2
    for r in mine:
        assert r["kind"] in ("compiled", "cache-load")
        assert r["seconds"] > 0.0
        assert t0 <= r["t"] <= utrace.wallclock()
    assert mine[0]["differs"] == []
    assert mine[1]["differs"] == ["arg 1 dim 0: 4 -> 16"]
    # the counts the benchmark reads are kept as they were
    assert sum(n for (prog, _), n in watchdog.counts.items()
               if prog == "compile_record_probe") == 2
    assert watchdog.compile_count() >= 2
    assert watchdog.records.maxlen == sanitize.MAX_COMPILE_RECORDS


def test_a_load_from_the_persistent_cache_is_told_from_a_compile(
        watchdog, tmp_path):
    """The same program compiled, dropped from the in-process cache and
    called again with the persistent cache on: the first record is a
    compile, the second a cache load."""
    import jax
    import jax.numpy as jnp
    from kubetpu.utils import compilation

    def cache_kind_probe(x):
        return jnp.tanh(x @ x).sum()
    with compilation.cache_subdir(str(tmp_path / "cache")):
        prev = jax.config.jax_persistent_cache_min_compile_time_secs
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        try:
            jax.jit(cache_kind_probe)(jnp.ones((32, 32)))
            jax.clear_caches()
            jax.jit(cache_kind_probe)(jnp.ones((32, 32)))
        finally:
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prev)
    kinds = [r["kind"] for r in watchdog.records
             if r["program"] == "cache_kind_probe"]
    assert kinds == ["compiled", "cache-load"]


def test_a_compile_forced_inside_a_cycle_is_an_event_of_that_cycle(
        watchdog):
    """A batch of a new bucket at toy size: the cycle that meets it
    carries one xla-compile event per program it had to build, each with
    kind, seconds > 0 and, for a program compiled before, a ``differs``
    that names the batch dimension; the cycle's meta says which bucket."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=8, max_spans_per_cycle=64)
    store = ClusterStore()
    for n in hollow.make_nodes(16):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=64, mode="gang",
        prewarm=False), async_binding=False)
    try:
        for wave, n in enumerate((5, 40)):     # batch buckets 8, 64
            for p in hollow.make_pods(n, prefix=f"w{wave}-"):
                store.add(p)
            sched.schedule_pending(timeout=0.0)
    finally:
        sched.close()
        utrace.disarm_flight_recorder()
    cycles = [c.to_dict() for c in fr.cycles()]
    assert len(cycles) == 2

    def auction_events(c):
        return [e for e in c["events"] if e["name"] == "xla-compile"
                and "schedule_gang" in e["args"]["program"]]
    first, second = (auction_events(c) for c in cycles)
    assert len(first) == 1 and first[0]["args"]["differs"] == []
    assert len(second) == 1
    a = second[0]["args"]
    assert a["kind"] in ("compiled", "cache-load") and a["seconds"] > 0
    # the batch axis went from the 8 bucket to the 64 bucket
    assert any(d.endswith("dim 0: 8 -> 64") for d in a["differs"]), a
    assert cycles[1]["t0"] <= a["t"] <= cycles[1]["t1"]
    # the event hangs under the phase that called the program
    parent = next(s for s in cycles[1]["spans"]
                  if s["id"] == second[0]["parent"])
    assert parent["name"] == "dispatch"
    assert cycles[1]["meta"]["pod_bucket"] >= 8
    # and the watchdog's own list has the same record
    assert any(r["program"] == a["program"] and r["differs"]
               == a["differs"] for r in watchdog.records)


# ----------------------------------------------- the served programs' names


@pytest.fixture(scope="module")
def served_programs():
    """The names jax compiles the served programs under, from one toy
    world that meets all four: a cycle (auction), churn before a second
    (delta scatter), an unschedulable pod (explain), and a preemptor
    (the what-if wave)."""
    import jax
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.client.store import ClusterStore
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    jax.clear_caches()
    wd = sanitize.install_compile_watchdog()
    wd.reset()
    store = ClusterStore()
    for i in range(3):
        store.add(hollow.make_node(f"node-{i}", cpu_milli=2000))
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
        prewarm=False), async_binding=False)
    try:
        for i in range(3):
            store.add(hollow.make_pod(f"low-{i}", cpu_milli=1500,
                                      priority=1))
        sched.schedule_pending(timeout=0.0)
        store.delete(store.get_pod("default", "low-0"))
        store.add(hollow.make_pod("low-3", cpu_milli=1500, priority=1))
        sched.schedule_pending(timeout=0.0)
        store.add(hollow.make_pod("too-big", cpu_milli=999999))
        store.add(hollow.make_pod("high", cpu_milli=1800, priority=100))
        sched.schedule_pending(timeout=0.0)
        names = sorted({prog for prog, _ in wd.counts})
    finally:
        sched.close()
        sanitize.uninstall_compile_watchdog(wd)
    return names


def _constants():
    from kubetpu.models import gang, programs
    return {"auction": gang.AUCTION_PROGRAM,
            "delta": programs.DELTA_PROGRAM,
            "explain": programs.EXPLAIN_PROGRAM,
            "whatif": programs.WHATIF_PROGRAM}


@pytest.mark.parametrize("which", ["auction", "delta", "explain", "whatif"])
def test_a_served_program_compiles_under_its_pinned_name(served_programs,
                                                         which):
    """The trace readers find a program's device time by a substring of
    its module's name; a rename here must fail a test, not empty a
    metric."""
    want = _constants()[which]
    assert any(want in name for name in served_programs), (
        want, served_programs)


@pytest.mark.parametrize("which", ["auction", "delta", "explain"])
def test_the_lowered_module_carries_the_pinned_name(which):
    """...and the module jax lowers -- what a profiler's ``XLA Modules``
    line shows -- carries it too."""
    import jax
    import numpy as np
    from kubetpu.framework.types import NodeInfo, PodInfo
    from kubetpu.harness import hollow
    from kubetpu.models import gang, programs
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder, gather_delta
    infos = [NodeInfo(n) for n in hollow.make_nodes(4)]
    pinfos = [PodInfo(p) for p in hollow.make_pods(3)]
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    host = sb.build(infos)
    cluster = host.to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    cfg = programs.ProgramConfig(
        filters=("NodeResourcesFit",),
        scores=(("NodeResourcesLeastAllocated", 1),), hostname_topokey=0)
    if which == "auction":
        lowered = gang._schedule_gang.lower(cluster, batch, cfg,
                                            jax.random.PRNGKey(0))
    elif which == "delta":
        lowered = programs._apply_cluster_delta_donated.lower(
            cluster, gather_delta(host, [0], []))
    else:
        lowered = programs._explain_verdicts.lower(cluster, batch, cfg)
    header = lowered.as_text().split("\n", 1)[0]
    assert header.startswith("module @jit_")
    assert _constants()[which] in header, header


def test_the_benchmarks_reader_matches_the_pinned_auction_name():
    from kubetpu.models import gang
    from perfbench.lib import readers, spans
    assert readers.AUCTION_PROGRAM == spans.AUCTION_PROGRAM \
        == gang.AUCTION_PROGRAM
