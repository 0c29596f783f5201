"""``correct``: the two comparisons with the plain reference, each number
printed beside its limit.

(a) the client's own event log of the whole run, replayed in observed
    order against every guarantee the configuration states;
(b) once the window has closed, one more cycle of the timed program at
    the timed sizes, over a cluster the reference knows exactly: the
    cell's own scheduler configuration (gang auction, the cell's batch
    size), the cell's nodes and init pods, as many resident pods as the
    window's population can reach, and one full batch of
    measured-template pods drawn from the seed.  Every placement must
    lie in the reference's feasible, score-maximal set of some round of
    that auction (``gang_misses`` of the configuration's reference).

Both limits are 0: they are exact comparisons.

What the residents and the sample of (b) carry: they are records of the
measured template under the roles "resident" and "sample", so their
names differ (``resident-<i>``, ``sample-<i>``).  A template that states
its labels and selectors literally gives every role the same labels and
terms, so a sample pod's terms count the residents exactly as a measured
pod's terms count the window's residents.  A ``features`` template
labels a pod ``group=<role>``, which is what its ``aff`` and ``spread``
selectors select: under it a sample pod's term of those two kinds counts
no resident (the two cells that exist use neither).

Why (b) is a cycle of its own and not the window's binds: the client
knows the cluster a window's cycle started from only to within a cycle
(its deletes race the scheduler's snapshot), and with as many residents
as a batch holds every pod that is not an init pod was bound within a
cycle of any bind.  A tie-set condition that allows for that has
nothing left to compare.  The window's own binds are held to (a).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

from . import world

LIMIT_VIOLATIONS = 0
LIMIT_GANG_MISSES = 0


def check_cluster(cell, ref, seed: int, nodes, init):
    """The cluster of check (b): the nodes, the init pods, and the
    largest measured population a window's cycle can start from
    (``resident_bound`` bound and one batch more not yet departed),
    placed by the reference's own float64 auction from the seed.
    Returns (reference cluster, [(pod record, node)...] bound)."""
    cluster = ref.Cluster(nodes)
    bound = list(init)
    for rec, node in init:
        cluster.add(rec, node)
    rng = np.random.default_rng([int(seed), 0xB0B])
    n = (int(cell.traffic["resident_bound"])
         + int(cell.config["scheduler"]["batch_size"]))
    resident = [world.measured_record(cell.config, "resident", i)
                for i in range(n)]
    placed = ref.auction_schedule(cluster, resident, rng)
    bound.extend((rec, placed[rec.name]) for rec in resident
                 if placed[rec.name])
    return cluster, bound


def sample_records(cell, seed: int) -> List[Any]:
    """One full batch of measured-template pods, drawn from the seed."""
    n = int(cell.config["scheduler"]["batch_size"])
    rng = np.random.default_rng([int(seed), 0x5A3])
    idx = rng.choice(1_000_000, size=n, replace=False)
    return [world.measured_record(cell.config, "sample", int(i))
            for i in idx]


def program_gang_cycle(cell, seed: int, nodes, bound,
                       sample) -> Dict[str, str]:
    """The timed program over that cluster: {pod name: node or ""}.  The
    whole batch is in the queue before the first pop, so it is one cycle
    of the window's own size."""
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils.metrics import SchedulerMetrics
    from .drive import scheduler_seed
    store = world.build_store(nodes, bound)
    sched = Scheduler(
        store, config=world.scheduler_config(
            cell.config["scheduler"], cell.config.get("mesh_shape")),
        metrics=SchedulerMetrics(), seed=scheduler_seed(seed),
        async_binding=False)
    try:
        for rec in sample:
            store.add(world.api_pod(rec))
        while sched.schedule_pending(timeout=0.2):
            pass
    finally:
        sched.close()
    return {rec.name: (store.get_pod("default", rec.name).spec.node_name
                       or "") for rec in sample}


def gang_check(cell, seed: int, nodes, init) -> List[str]:
    """Check (b): the misses, as the reference words them."""
    ref = cell.reference()
    cluster, bound = check_cluster(cell, ref, seed, nodes, init)
    sample = sample_records(cell, seed)
    placements = program_gang_cycle(cell, seed, nodes, bound, sample)
    return ref.gang_misses(cluster, sample, placements)


def decide(cell, seed: int, nodes, init, records: Dict[str, Any], cl,
           store, stuck: Sequence[str]) -> Tuple[bool, List[str]]:
    ref = cell.reference()
    lines = []
    # (a)
    t = time.perf_counter()
    readback = {}
    for name in {e[1] for e in cl.log if e[0] == "bind"}:
        pod = store.get_pod("default", name)
        readback[name] = pod.spec.node_name if pod is not None else None
    violations = ref.replay(nodes, init, records, list(cl.log), readback,
                            stuck)
    n_binds = sum(1 for e in cl.log if e[0] == "bind")
    lines.append(f"correct (a) guarantee violations over {n_binds} binds, "
                 f"{len(stuck)} given up on: {len(violations)}  limit "
                 f"{LIMIT_VIOLATIONS}  ({time.perf_counter() - t:.2f} s)")
    lines.extend(f"  violation: {v}" for v in violations[:5])
    # (b)
    t = time.perf_counter()
    misses = gang_check(cell, seed, nodes, init)
    lines.append(f"correct (b) placements outside every round's tie set, "
                 f"of one gang cycle of "
                 f"{cell.config['scheduler']['batch_size']}: {len(misses)}  "
                 f"limit {LIMIT_GANG_MISSES}  "
                 f"({time.perf_counter() - t:.2f} s)")
    lines.extend(f"  miss: {m}" for m in misses[:5])
    ok = (len(violations) <= LIMIT_VIOLATIONS
          and len(misses) <= LIMIT_GANG_MISSES)
    return ok, lines
