"""Runtime race-harness tests: the 8-thread stress gate plus proof the
harness actually catches seeded violations.

The stress test is the dynamic mirror of the kubelint concurrency
tree-clean gate: queue push/pop_batch + cache add/remove/cleanup + store
fan-out hammered from 8 threads, 50 consecutive iterations, zero
violations AND zero recompiles (the workload is host-only, so any compile
at all means something leaked onto the device path).  `make race-test`
runs this file under KUBETPU_RACE=1; in plain tier-1 the tests arm the
harness themselves via racechecked(), which is the same code path."""

import threading

import pytest

from kubetpu.api import types as api
from kubetpu.utils import racecheck
from kubetpu.utils.sanitize import sanitized

ITERATIONS = 50
THREADS = 8
OPS = 30


def _pod(name, node=""):
    p = api.Pod(metadata=api.ObjectMeta(name=name, namespace="d"))
    if node:
        p.spec.node_name = node
    return p


def _node(name):
    n = api.Node(metadata=api.ObjectMeta(name=name))
    n.status.allocatable = {"cpu": "4", "memory": "8Gi", "pods": "110"}
    return n


def _hammer(fns, errors):
    threads = [threading.Thread(target=_trap, args=(fn, errors), name=f"h{i}")
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads), "stress thread hung"


def _trap(fn, errors):
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — surfaced by the main thread
        errors.append(e)


def test_stress_8_threads_50_iterations_clean():
    """Acceptance gate: 50 consecutive iterations of an 8-thread hammer
    over queue + cache + store with zero violations and zero recompiles."""
    from kubetpu.client.store import ClusterStore
    from kubetpu.schedqueue.queue import SchedulingQueue
    from kubetpu.state.cache import SchedulerCache, Snapshot

    with sanitized() as watchdog, racechecked_relaxed_hold() as reg:
        for it in range(ITERATIONS):
            store = ClusterStore()
            cache = SchedulerCache()
            queue = SchedulingQueue()
            # store fan-out -> queue, the scheduler's handler shape
            store.subscribe(
                "Pod", lambda ev, old, new:
                queue.add(new) if ev == "add" and new is not None
                and not new.spec.node_name else None)
            for j in range(4):
                cache.add_node(api.Node(
                    metadata=api.ObjectMeta(name=f"n{j}")))
            errors = []

            def pusher(base):
                def run():
                    for k in range(OPS):
                        store.add(_pod(f"it{it}-p{base}-{k}"))
                return run

            def popper():
                for _ in range(OPS):
                    for qp in queue.pop_batch(4, timeout=0):
                        queue.add_unschedulable_if_not_present(
                            qp, qp.scheduling_cycle)

            def cache_churn(base):
                def run():
                    for k in range(OPS):
                        p = _pod(f"it{it}-c{base}-{k}", node=f"n{k % 4}")
                        cache.assume_pod(p)
                        cache.finish_binding(p, now=0.0)
                        if k % 3 == 0:
                            try:
                                cache.forget_pod(p)
                            except ValueError:
                                # the OTHER churn thread's cleanup expired
                                # it first — a legitimate interleaving
                                pass
                        else:
                            # TTL of 30s from now=0 long expired
                            cache.cleanup_assumed_pods(now=1e9)
                return run

            def snapshotter():
                snap = Snapshot()
                for _ in range(OPS):
                    cache.update_snapshot(snap)
                    cache.pod_count()

            def nominator():
                for k in range(OPS):
                    p = _pod(f"it{it}-nom-{k}")
                    queue.add_nominated_pod(p, f"n{k % 4}")
                    queue.nominated_pods_for_node(f"n{k % 4}")
                    queue.delete_nominated_pod_if_exists(p)
                    len(queue)

            _hammer([pusher(0), pusher(1), popper,
                     cache_churn(0), cache_churn(1),
                     snapshotter, nominator,
                     lambda: [store.list("Pod") for _ in range(OPS)]],
                    errors)
            assert not errors, errors
            vs = reg.snapshot()
            assert not vs, ("iteration %d: %d violation(s):\n%s"
                            % (it, len(vs),
                               "\n".join(str(v) for v in vs)))
            queue.close()
            cache.close()
        watchdog.assert_no_recompilation()
        assert watchdog.compile_count() == 0, \
            "host-only stress compiled a device program"


def test_bind_transactions_against_adds_and_deletes_are_clean():
    """PR 41's batch forms under the harness: ``bind_many`` transactions
    delivered whole to a list-taking subscriber that confirms them in the
    cache and the queue (``confirm_pods``, ``pods_bound``) and settles
    them (``finish_binding_many``), the job's events in one
    ``add_many``, while other threads add, delete and list pods and a
    per-event subscriber deletes what it hears bound."""
    from kubetpu.client.store import ClusterStore
    from kubetpu.schedqueue.queue import SchedulingQueue
    from kubetpu.state.cache import SchedulerCache
    from kubetpu.utils.events import EventBroadcaster

    with racechecked_relaxed_hold() as reg:
        for it in range(10):
            store, cache = ClusterStore(), SchedulerCache()
            queue = SchedulingQueue()
            recorder = EventBroadcaster(sink=store).new_recorder()
            for j in range(4):
                store.add(_node(f"n{j}"))
                cache.add_node(_node(f"n{j}"))
            confirmed, deleted = [], []

            def on_pods(events):
                bound = [new for ev, old, new in events
                         if ev == "update" and new.spec.node_name
                         and not old.spec.node_name]
                for ev, old, new in events:
                    if ev == "add" and not new.spec.node_name:
                        queue.add(new)
                    elif ev == "delete":
                        queue.delete(old)
                        if old.spec.node_name:
                            cache.remove_pod(old)
                if bound:
                    confirmed.append(len(bound) - cache.confirm_pods(bound))
                    queue.pods_bound(bound)

            def on_pod(ev, old, new):
                if (ev == "update" and new.spec.node_name
                        and new.metadata.name.endswith("3")):
                    store.delete(new)
                    deleted.append(new.metadata.name)
            store.subscribe("Pod", on_pods, batched=True)
            store.subscribe("Pod", on_pod)
            errors = []

            def binder(base):
                def run():
                    for k in range(6):
                        pods = [_pod(f"it{it}-b{base}-{k}-{i}")
                                for i in range(5)]
                        for p in pods:
                            store.add(p)
                        queue.pop_batch(8, timeout=0)
                        assumed = []
                        for i, p in enumerate(pods):
                            a = api.shallow_copy(p)
                            a.spec = api.shallow_copy(p.spec)
                            a.spec.node_name = f"n{i % 4}"
                            cache.assume_pod(a)
                            assumed.append(a)
                        got = store.bind_many(
                            [(p, a.spec.node_name)
                             for p, a in zip(pods, assumed)])
                        assert got == [None] * 5, got
                        cache.finish_binding_many(assumed, now=0.0)
                        recorder.events([(p, "Normal", "Scheduled", "ok")
                                         for p in pods])
                return run

            def churn(base):
                def run():
                    for k in range(OPS):
                        p = _pod(f"it{it}-c{base}-{k}")
                        store.add(p)
                        store.list("Pod")
                        store.delete(p)
                return run

            _hammer([binder(0), binder(1), churn(0), churn(1),
                     lambda: [cache.pod_count() or len(queue)
                              for _ in range(OPS)]], errors)
            assert not errors, errors
            vs = reg.snapshot()
            assert not vs, "\n".join(str(v) for v in vs)
            # every bind was one this cache had assumed, and confirmed
            assert sum(confirmed) == 60 and not cache.assumed_pods
            assert len(deleted) == 12 == 60 - cache.pod_count()
            assert len(store.list("Event")) == 60 and len(queue) == 0
            queue.close()
            cache.close()


def racechecked_relaxed_hold():
    """Stress iterations share one armed scope; CI boxes can stall a
    thread scheduler tick, so the hold threshold is generous — the
    held-too-long rule has its own dedicated test below."""
    return racecheck.racechecked(strict=False, hold_ms=5000)


def test_seeded_unguarded_mutation_is_reported():
    """The harness demonstrably catches what it claims to: an unguarded
    mutation of a cache map from a foreign thread is reported."""
    from kubetpu.state.cache import SchedulerCache

    with racecheck.racechecked(strict=False) as reg:
        cache = SchedulerCache()

        def rogue():
            cache.assumed_pods["ghost"] = True      # no lock: violation

        t = threading.Thread(target=rogue)
        t.start()
        t.join()
        vs = [v for v in reg.snapshot() if v.kind == "unguarded-mutation"]
        assert vs, "seeded unguarded mutation was not reported"
        assert "assumed_pods" in vs[0].message
        assert "_lock" in vs[0].message


def test_seeded_rebind_is_reported():
    from kubetpu.state.cache import SchedulerCache

    with racecheck.racechecked(strict=False) as reg:
        cache = SchedulerCache()
        cache.pod_states = {}       # rebind of a guarded attr, no lock
        assert any(v.kind == "unguarded-mutation"
                   and "pod_states" in v.message for v in reg.snapshot())


def test_locked_mutations_are_clean():
    from kubetpu.state.cache import SchedulerCache

    with racecheck.racechecked() as reg:
        cache = SchedulerCache()
        p = _pod("ok", node="n1")
        cache.add_node(_node("n1"))
        cache.add_pod(p)
        cache.remove_pod(p)
        assert not reg.snapshot()


def test_lock_order_inversion_is_reported():
    with racecheck.racechecked(strict=False) as reg:
        a = racecheck._LockProxy(threading._allocate_lock(), "roleA")
        b = racecheck._LockProxy(threading._allocate_lock(), "roleB")
        with a:
            with b:
                pass
        with b:
            with a:
                pass
        vs = [v for v in reg.snapshot() if v.kind == "lock-order"]
        assert vs, "inverted acquisition order was not reported"
        assert "roleA" in vs[0].message and "roleB" in vs[0].message


def test_held_too_long_is_reported():
    import time

    with racecheck.racechecked(strict=False, hold_ms=10) as reg:
        lock = racecheck._LockProxy(threading._allocate_lock(), "slow")
        with lock:
            time.sleep(0.05)
        vs = [v for v in reg.snapshot() if v.kind == "held-too-long"]
        assert vs, "a 50 ms hold above a 10 ms threshold was not reported"


def test_condition_wait_releases_held_tracking():
    """queue.pop blocking on its condition must not count as holding the
    lock (wait releases it) — otherwise every waiter trips hold-time."""
    from kubetpu.schedqueue.queue import SchedulingQueue

    with racecheck.racechecked(hold_ms=100) as reg:
        queue = SchedulingQueue()

        def late_add():
            import time
            time.sleep(0.3)
            queue.add(_pod("wakeup"))

        t = threading.Thread(target=late_add)
        t.start()
        got = queue.pop(timeout=5.0)
        t.join()
        assert got is not None
        held = [v for v in reg.snapshot() if v.kind == "held-too-long"]
        assert not held, "\n".join(str(v) for v in held)


def test_harness_disarms_cleanly():
    """After the scoped harness exits, new locks are plain and guarded
    classes mutate freely — the serving path pays nothing."""
    from kubetpu.state.cache import SchedulerCache

    with racecheck.racechecked(strict=False):
        pass
    if not racecheck.race_enabled():
        lk = threading.Lock()
        assert not isinstance(lk, racecheck._LockProxy)
        cache = SchedulerCache()
        cache.assumed_pods["free"] = True       # disarmed: no check
        assert not racecheck.registry().snapshot()
