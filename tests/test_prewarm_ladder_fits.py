"""The prewarm ladder ends where the device's memory does (PR 35): at
150,000 bound pods the rung after the resident 262,144-row pod axis is
8.9 GB of cluster and the one after it 17.8 GB of a 16 GB chip, so a
rung is dry-run only if its cluster, and as much again for what runs on
it, fits beside what is resident.  A backend that reports no limit (the
CPU) is held to none."""

import jax
import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler


class _Jax:
    """The jax module with one device that reports ``stats``."""

    def __init__(self, stats):
        self._stats = stats

    def devices(self):
        stats = self._stats

        class Device:
            def memory_stats(self):
                return stats
        return [Device()]

    def __getattr__(self, name):
        return getattr(jax, name)


@pytest.fixture(scope="module")
def sched():
    store = ClusterStore()
    for i, n in enumerate(hollow.make_nodes(6)):
        store.add(n)
        for p in hollow.make_pods(5, prefix=f"bound-{i}-"):
            p.spec.node_name = n.name
            store.add(p)
    s = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang",
        prewarm_ladder=2), async_binding=False)
    yield s
    s.close()


def _buckets(sched, stats):
    sched._jax = _Jax(stats)
    try:
        sched.prewarm_report.clear()
        assert sched.prewarm() is True
        return [bucket for bucket, _ in sched.prewarm_report]
    finally:
        sched._jax = jax


def test_the_ladder_ends_at_the_last_rung_the_device_can_hold(sched,
                                                              monkeypatch):
    # what the rule is asked: (bytes of the cluster it grows from, rung)
    asked = []
    fits = Scheduler._rung_fits

    def spy(self, cluster, bucket):
        asked.append((cluster.nbytes, bucket))
        return fits(self, cluster, bucket)
    monkeypatch.setattr(Scheduler, "_rung_fits", spy)
    # 30 bound pods: a 32-row pod axis, then the rungs of 64 and 128
    assert _buckets(sched, None) == [32, 64, 128]
    assert [b for _, b in asked] == [64, 128]
    base = asked[0][0]
    assert base < asked[1][0]                  # the pod axis doubled
    assert _buckets(sched, {}) == [32, 64, 128]
    roomy = {"bytes_limit": 1 << 40, "bytes_in_use": 123}
    assert _buckets(sched, roomy) == [32, 64, 128]
    # a rung counts its cluster (the one it grows from, scaled by the pod
    # axis) twice: room for the first rung's 2 x 2 x base beside what is
    # in use, and not for the second's
    one_rung = {"bytes_limit": 1000 + 4 * base, "bytes_in_use": 1000}
    assert _buckets(sched, one_rung) == [32, 64]
    # one byte less: no rung
    assert _buckets(sched, dict(one_rung, bytes_in_use=1001)) == [32]
    assert _buckets(sched, {"bytes_limit": 1}) == [32]
