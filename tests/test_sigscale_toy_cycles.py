"""Every cycle's record of ``sigscale-150k``'s toy (48 nodes x 29 init
pods, batches of 64) through a whole traced run of the harness, as the
tensorizer fills it since PR 44: a refresh visits and sends what CHANGED.

The twin of ``tests/perfbench/test_perfbench_sigscale.py::
test_every_cycle_of_the_toy_run_walks_its_dirty_nodes_whole``, which holds
the record to PR 35's meaning (``29 x node_rows_dirty <= pods_walked ==
pod_rows_seen == pods_copied``) in a file a PR that changes the program
may not edit: ``tests/conftest.py`` skips that one test until a
``benchmark`` PR restates it there.  Every line of it that still holds is
kept here on the same toy run (its fixtures, by import); the two that
changed are restated."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "perfbench"))
from test_perfbench_sigscale import toy_root, toy_traced  # noqa: E402,F401

BATCH = 64


def test_every_cycle_of_the_toy_run_visits_and_sends_what_changed(
        toy_traced):
    """k dirty nodes of m pods (m the 29 init pods and the measured pods
    beside them, at most the eleven a node has room for): the snapshot
    still clones k x m, the delta build visits the arrivals alone (no pod
    of the toy owns a term) and sends the rows it refilled or cleared."""
    res, ctx, said = toy_traced
    assert res["correct"] is True and res["failed"] == 0, said
    builds = changed = 0
    for c in ctx["cycles"]:
        assert c["meta"]["pod_rows_live"] <= c["meta"]["pod_bucket"] == 2048
        assert c["meta"]["cluster_device_bytes"] > 0
        spans = {s["name"]: s for s in c["spans"]}
        copied = spans["snapshot"]["args"]["pods_copied"]
        assert 0 <= copied <= 48 * 40
        if "delta-build" not in spans:
            continue
        a = spans["delta-build"]["args"]
        builds += 1
        # the snapshot cloned the nodes the build then found dirty, whole
        assert 29 * a["node_rows_dirty"] <= copied \
            <= 40 * a["node_rows_dirty"]
        # restated: one visit an arrival, none for a pod that stayed
        assert a["pods_walked"] == a["pod_rows_refilled"] <= BATCH
        assert a["pods_walked"] <= copied
        # restated: the delta's pod rows are the refilled and the cleared
        # (a freed row an arrival took counts once); a cleared row is a
        # bound pod the client deleted, at most what a cycle or two bound
        assert a["pod_rows_refilled"] <= a["pod_rows_seen"] \
            <= a["pod_rows_refilled"] + 2 * BATCH
        # every changed row lies on a dirty node
        if a["pod_rows_seen"]:
            assert a["node_rows_dirty"] > 0
        assert c["meta"]["delta_rows"] \
            == a["node_rows_dirty"] + a["pod_rows_seen"]
        # one pod-row bucket for every steady cycle: four batch buckets
        assert c["meta"]["delta_buckets"][1] == 4 * BATCH
        changed += a["pod_rows_seen"]
    assert builds and changed
