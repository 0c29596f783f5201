"""Profile the gang auction device program across node scales.

Measures, for the IPA-heavy north-star workload at fixed B=4096 pending:
  - steady-state device time per cycle (host clock around the packed
    readback, which ends only when the program is done)
  - auction round count (the while_loop trip count)
  - per-round device time (device_s / rounds)

Usage: python tools/profile_gang.py [nodes ...]
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from kubetpu.utils.compilation import enable_persistent_cache

enable_persistent_cache()

import jax  # noqa: E402

from bench import build_world  # noqa: E402
from kubetpu.api import types as api  # noqa: E402
from kubetpu.framework.types import PodInfo  # noqa: E402
from kubetpu.models import programs  # noqa: E402
from kubetpu.models.batch import PodBatchBuilder  # noqa: E402
from kubetpu.models.gang import schedule_gang  # noqa: E402
from kubetpu.scheduler import Scheduler  # noqa: E402
from kubetpu.state.tensors import SnapshotBuilder  # noqa: E402
from kubetpu.apis.config import (KubeSchedulerConfiguration,  # noqa: E402
                                 KubeSchedulerProfile)


def profile_shape(n_nodes: int, n_pods: int = 4096, ipa_heavy: bool = True):
    store, pending = build_world(n_nodes, n_pods, existing_per_node=1,
                                 ipa_heavy=ipa_heavy)
    cfg_k = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                       batch_size=n_pods, mode="gang")
    sched = Scheduler(store, config=cfg_k, async_binding=False)
    sched.cache.update_snapshot(sched.snapshot)
    node_infos = sched.snapshot.node_info_list
    fwk = next(iter(sched.profiles.values()))
    pinfos = [PodInfo(p) for p in pending]
    sb = SnapshotBuilder(hard_pod_affinity_weight=fwk.hard_pod_affinity_weight)
    sb.intern_pending(pinfos)
    cluster = sb.build(node_infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(pinfos))
    keys = Scheduler._batch_topo_keys(sb.table, pinfos)
    cfg = programs.ProgramConfig(
        filters=fwk.tensor_filters, scores=fwk.tensor_scores,
        hostname_topokey=max(sb.table.topokey.get(api.LABEL_HOSTNAME), 0),
        plugin_args=fwk.tensor_plugin_args(sb.table),
        active_topo_keys=keys)
    rng = jax.random.PRNGKey(1)

    P = int(cluster.pod_valid.shape[0])
    N = int(cluster.allocatable.shape[0])
    print(f"nodes={N} pod_axis={P} batch={batch.valid.shape[0]} "
          f"active_keys={keys}", flush=True)

    t0 = time.time()
    res = schedule_gang(cluster, batch, cfg, rng)
    rounds = int(np.asarray(res.rounds))
    first = time.time() - t0
    # steady state: 3 reps, readback-timed
    times = []
    for i in range(3):
        t0 = time.time()
        res = schedule_gang(cluster, batch, cfg,
                            jax.random.fold_in(rng, i))
        np.asarray(res.packed)
        times.append(time.time() - t0)
    chosen = np.asarray(res.chosen)
    dev = min(times)
    print(f"  first={first:.2f}s steady={dev:.3f}s rounds={rounds} "
          f"per_round={dev / max(rounds, 1) * 1e3:.1f}ms "
          f"scheduled={(chosen >= 0).sum()}", flush=True)

    def variant(label, **kw):
        t0 = time.time()
        r = schedule_gang(cluster, batch, cfg, rng, **kw)
        rr = int(np.asarray(r.rounds))
        f = time.time() - t0
        ts = []
        for i in range(2):
            t0 = time.time()
            r = schedule_gang(cluster, batch, cfg,
                              jax.random.fold_in(rng, 10 + i), **kw)
            np.asarray(r.packed)
            ts.append(time.time() - t0)
        print(f"  {label}: first={f:.2f}s steady={min(ts):.3f}s rounds={rr}",
              flush=True)

    if "--variants" in sys.argv:
        variant("max_rounds=1", max_rounds=1)
        variant("max_rounds=2", max_rounds=2)
        variant("no_topo", intra_batch_topology=False)

    if "--plugins" in sys.argv:
        # marginal cost of each score plugin: drop one at a time, 2 rounds
        def run_cfg(label, c):
            t0 = time.time()
            r = schedule_gang(cluster, batch, c, rng, max_rounds=2)
            np.asarray(r.packed)   # drain the device before steady timing
            f = time.time() - t0
            ts = []
            for i in range(2):
                t0 = time.time()
                r = schedule_gang(cluster, batch, c,
                                  jax.random.fold_in(rng, 99 + i),
                                  max_rounds=2)
                np.asarray(r.packed)
                ts.append(time.time() - t0)
            s = min(ts)
            print(f"  {label}: first={f:.1f}s steady={s:.3f}s", flush=True)
            return s

        base_s = run_cfg("all_scores", cfg)
        for name, _ in cfg.scores:
            c = cfg._replace(scores=tuple((n, w) for n, w in cfg.scores
                                          if n != name))
            s = run_cfg(f"-{name}", c)
            print(f"    marginal {name}: {(base_s - s) * 1e3:.0f}ms/2rounds",
                  flush=True)
        run_cfg("no_scores", cfg._replace(scores=()))
        run_cfg("no_filters_no_scores",
                cfg._replace(scores=(), filters=("NodeResourcesFit",)))
    sched.close()
    return dict(nodes=N, pod_axis=P, device_s=dev, rounds=rounds)


if __name__ == "__main__":
    shapes = [int(x) for x in sys.argv[1:]
              if not x.startswith("--")] or [1024, 2048, 5120]
    for n in shapes:
        profile_shape(n)
