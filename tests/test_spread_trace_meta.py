"""What a gang cycle says about its batch's hard spread constraints
(PR 33): on the cycle's meta ``spread_constraints`` (valid DoNotSchedule
rows), ``spread_buckets`` ([C, Us]: the constraint and unique-selector
buckets the auction's recount ran over) and ``needs_topo``; on the
``batch-build`` span ``spread_rows``.  A plain batch says 0 / 0.  Since
PR 43 ``spread_late_admits``: the auction's own count of the pods a round
admitted at their TURN that the round-start rule would have held back."""

import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import trace as utrace


def _cycle_of(pods, others=(), armed=True):
    """The record of the one gang cycle that places ``pods`` on twelve
    nodes in three zones; ``others``: objects (Services, ...) the store
    holds before the pods arrive.  Disarmed: None, once the pods are
    placed all the same."""
    store = ClusterStore()
    for obj in list(hollow.make_nodes(12, zones=3)) + list(others):
        store.add(obj)
    utrace.disarm_flight_recorder()
    flight = (utrace.arm_flight_recorder(capacity=8, max_spans_per_cycle=64)
              if armed else None)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang"),
        async_binding=False)
    try:
        for p in pods:
            store.add(p)
        while sched.schedule_pending(timeout=0.2):
            pass
        records = [c.to_dict() for c in flight.cycles()] if armed else None
    finally:
        sched.close()
        utrace.disarm_flight_recorder()
    assert all(store.get_pod("default", p.metadata.name).spec.node_name
               for p in pods)
    if not armed:
        return None
    assert len(records) == 1
    return records[0]


def _blue(i, **spread):
    p = hollow.make_pod(f"blue-{i}", labels={"color": "blue"})
    return hollow.with_spread(p, api.LABEL_ZONE, **spread) if spread else p


@pytest.mark.parametrize("what,pods,want", [
    ("every pod one hard zone constraint",
     [_blue(i, max_skew=5) for i in range(6)], (6, 1, 1)),
    ("a soft constraint is no hard row, and still needs the rounds",
     [_blue(i, max_skew=5, when="ScheduleAnyway") for i in range(6)],
     (0, 0, 1)),
    ("a plain batch", [hollow.make_pod(f"plain-{i}") for i in range(6)],
     (0, 0, 0))])
def test_a_gang_cycle_records_its_spread_constraints(what, pods, want):
    rows, selectors, needs_topo = want
    rec = _cycle_of(pods)
    meta = rec["meta"]
    assert meta["spread_constraints"] == rows
    assert meta["needs_topo"] == needs_topo
    c, us = meta["spread_buckets"]
    # buckets, padding included: one constraint a pod, one selector shared
    assert c == 1 and us >= max(selectors, 1)
    build = [s for s in rec["spans"] if s["name"] == "batch-build"]
    assert len(build) == 1 and build[0]["args"]["spread_rows"] == rows
    assert build[0]["args"]["pods"] == len(pods)
    # the unwindowed loop (six pods, a window of 512) ends on one round
    # that admits nothing, so a batch the rounds re-evaluate says two at
    # the least however many a round admits
    assert meta["auction_rounds"] >= (2 if needs_topo else 1)


@pytest.mark.parametrize("what,max_skew,admitting,late", [
    # since PR 34 a round admits a zone's whole room, the filter's own
    # slack: from empty zones maxSkew 5 leaves room for five a zone, and
    # nobody needs more than the round's start offered
    ("six pods fit the room of one round", 5, (1, 1), (0, 0)),
    # maxSkew 1 leaves room for one a zone as a round starts; since PR 43
    # a pod fits at its turn wherever its zone stands at the minimum THEN:
    # a second pod of a zone gets in once the other two hold one
    # (the default scores herd the proposals, so it may well not happen)
    ("no room at the round's start: in at its turn", 1, (1, 6), (0, 5))])
def test_a_round_admits_a_zones_whole_room(what, max_skew, admitting, late):
    meta = _cycle_of([_blue(i, max_skew=max_skew) for i in range(6)])["meta"]
    lo, hi = admitting
    # + the one closing round that admits nothing, and at most one strict
    # round after a widened one that admitted nobody
    assert lo + 1 <= meta["auction_rounds"] <= 2 * hi + 1
    assert late[0] <= meta["spread_late_admits"] <= late[1]


def test_the_late_admits_word_is_said_only_where_there_is_one_to_say():
    # armed, a batch with a DoNotSchedule row: the word is there (above)
    # ... a soft or a plain batch has no hard constraint and no word
    soft = _cycle_of([_blue(i, max_skew=1, when="ScheduleAnyway")
                      for i in range(6)])["meta"]
    assert soft["needs_topo"] == 1 and "spread_late_admits" not in soft
    plain = _cycle_of([hollow.make_pod(f"plain-{i}")
                       for i in range(6)])["meta"]
    assert "spread_late_admits" not in plain
    # disarmed nothing is recorded and nothing is read back: the pods are
    # placed all the same (the helper asserts it)
    assert _cycle_of([_blue(i, max_skew=1) for i in range(6)],
                     armed=False) is None
    assert utrace.flight_recorder() is None
