"""The client against a fake store: it holds resident_bound, offers one
replacement per bind in a closed loop, and logs what it saw in order."""

import threading
import time
from types import SimpleNamespace

import pytest

from perfbench.lib import client


class FakeStore:
    """Binds every added pod on another thread after ``delay`` seconds,
    to node "n<k % 4>", and tells the subscribers like the real store."""

    def __init__(self, delay=0.0):
        self.delay = delay
        self.subs = []
        self.live = {}
        self.max_bound = 0
        self._lock = threading.Lock()
        self._k = 0

    def subscribe(self, kind, handler):
        self.subs.append(handler)

    def add(self, pod):
        with self._lock:
            self.live[pod.metadata.name] = pod
            k = self._k
            self._k += 1
        threading.Timer(self.delay, self._bind, (pod, f"n{k % 4}")).start()

    def _bind(self, pod, node):
        with self._lock:
            if pod.metadata.name not in self.live:
                return
            old = SimpleNamespace(spec=SimpleNamespace(node_name=""))
            pod.spec.node_name = node
            bound = sum(1 for p in self.live.values() if p.spec.node_name)
            self.max_bound = max(self.max_bound, bound)
        for h in self.subs:
            h("update", old, pod)

    def delete(self, pod):
        with self._lock:
            del self.live[pod.metadata.name]


def make_pool(n=64):
    def rec(i):
        return SimpleNamespace(name=f"measured-{i}")

    def obj(r):
        return SimpleNamespace(metadata=SimpleNamespace(name=r.name),
                               spec=SimpleNamespace(node_name=""))
    return client.PodPool(rec, obj, n)


def wait_for(cond, timeout=20.0):
    end = time.time() + timeout
    while time.time() < end:
        if cond():
            return True
        time.sleep(0.01)
    return False


def test_closed_loop_replaces_each_bind_and_holds_the_population():
    store = FakeStore(delay=0.002)
    traffic = {"kind": "closed", "depth": 12, "resident_bound": 5,
               "pool_pods_per_s": 10}
    cl = client.Client(store, traffic, make_pool())
    cl.start()
    assert wait_for(lambda: cl.bound_count() >= 60)
    cl.stop_offering()
    n = len(cl.order)
    assert wait_for(lambda: cl.pending_count() == 0)
    cl.stop()
    assert len(cl.order) == n                 # nothing offered once told
    # 12 pending at the start, then one offer per bind seen
    assert len(cl.replace_late) == n - 12
    assert all(late >= 0 for _, late in cl.replace_late)
    # never more than resident_bound bound beyond the binds in flight
    # (a replacement is offered before the departure it pays for: + 2)
    assert len(cl._resident) == 5
    assert store.max_bound <= 5 + 12 + 2
    assert cl.pool.built_late == max(0, n - 64)
    # the log: every add before its bind, every delete after it, a
    # delete logged before the store forgets the pod
    seen = {}
    for ev in cl.log:
        seen.setdefault(ev[1], []).append(ev[0])
    for name, kinds in seen.items():
        assert kinds in (["add", "bind"], ["add", "bind", "delete"]), name
    assert sum(1 for k in seen.values() if "delete" not in k) == 5


def test_an_open_loop_kind_has_no_driver_yet():
    with pytest.raises(NotImplementedError, match="no driver yet"):
        client.Client(FakeStore(), {"kind": "poisson",
                                    "rate_pods_per_s": 100,
                                    "resident_bound": 6},
                      make_pool(8))


def test_a_failure_on_the_client_thread_is_raised_by_stop():
    class Broken(FakeStore):
        def add(self, pod):
            raise RuntimeError("store is down")
    cl = client.Client(Broken(), {"kind": "closed", "depth": 2,
                                  "resident_bound": 1,
                                  "pool_pods_per_s": 1},
                       make_pool(8))
    cl.start()
    assert wait_for(lambda: cl.error is not None)
    with pytest.raises(RuntimeError, match="store is down"):
        cl.stop()


def test_a_dip_deletes_every_resident_and_the_population_refills():
    store = FakeStore(delay=0.002)
    traffic = {"kind": "closed", "depth": 8, "resident_bound": 6,
               "pool_pods_per_s": 10}
    cl = client.Client(store, traffic, make_pool(400))
    cl.start()
    assert wait_for(lambda: cl.bound_count() >= 20)
    deletes = sum(1 for e in cl.log if e[0] == "delete")
    cl.dip()
    assert wait_for(lambda: sum(1 for e in cl.log if e[0] == "delete")
                    >= deletes + 6)
    assert wait_for(lambda: len(cl._resident) == 6)
    cl.stop_offering()
    cl.stop()
    assert store.max_bound <= 6 + 8 + 2


def test_warm_up_waits_for_population_dips_and_quiet():
    from perfbench.lib import drive

    class FakeClient:
        resident_bound = 4
        error = None

        def __init__(self):
            self.t = 0.0
            self.dips = []

        def clock(self):
            self.t += 0.01
            return self.t

        def bound_count(self):
            return int(self.t * 100)          # 100 binds a "second"

        def dip(self):
            self.dips.append(self.bound_count())

        def surge(self, extra):
            self.surged = extra

    cl = FakeClient()
    compiles = {"n": 0}

    def compile_count():
        # a program arrives every 10 binds, the last at 50
        if cl.bound_count() < 60:
            compiles["n"] = cl.bound_count() // 10
        return compiles["n"]
    said = []
    traffic = {"depth": 8, "warmup": {"min_s": 0.0, "quiet_s": 0.1,
                                      "quiet_binds": 15, "dips": 2,
                                      "max_s": 50.0}}
    w = drive.Warmup(cl, traffic, compile_count, said.append, surge=3)
    import unittest.mock
    with unittest.mock.patch("time.sleep", lambda s: None):
        w.run()
    # first dip once depth + resident_bound are through, the second three
    # populations later, the end only after the last compile + quiet
    assert cl.surged == 3
    assert len(cl.dips) == 2 and cl.dips[0] >= 12
    assert cl.dips[1] >= cl.dips[0] + 12
    assert cl.bound_count() >= max(cl.dips[1] + 12, 50 + 15)
    assert "warm-up" in said[0]


def test_a_surge_lifts_the_population_once_and_lets_it_fall_back():
    store = FakeStore(delay=0.002)
    traffic = {"kind": "closed", "depth": 8, "resident_bound": 6,
               "pool_pods_per_s": 10}
    cl = client.Client(store, traffic, make_pool(400))
    cl.surge(5)
    cl.start()
    assert wait_for(lambda: cl._surge == 0 and cl.bound_count() >= 40)
    # reached at 11 bound, held for 12 more binds
    assert cl.bound_count() >= 11 + 12
    cl.stop_offering()
    assert wait_for(lambda: cl.pending_count() == 0)
    cl.stop()
    assert len(cl._resident) == 6
    assert 11 <= store.max_bound <= 11 + 8 + 2
