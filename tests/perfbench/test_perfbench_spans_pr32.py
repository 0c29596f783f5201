"""The four readers PR 32 added (perfbench/metrics/delta_build_, delta_apply_,
batch_build_ms_per_cycle.sat and mirror_rows_refilled_per_cycle.sat): each on
cycle records worked out by hand, on a record of a program that does not say
what it refilled, and through a whole traced run of the toy cell.  A file of
its own, beside test_perfbench_spans.py whose helpers it borrows: a PR that
changes the program adds files to the benchmark and edits none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import drive, spec
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
# the split of tensorize (the cluster delta's host half, its dispatch, the
# pod batch) and the mirror rows the host half rewrote
PR32 = {
    "delta_build_ms_per_cycle.sat": ("prepare", "program_span"),
    "delta_apply_ms_per_cycle.sat": ("prepare", "program_span"),
    "batch_build_ms_per_cycle.sat": ("prepare", "program_span"),
    "mirror_rows_refilled_per_cycle.sat": ("prepare", "program_span"),
}
ALL_CELLS = base.CELLS + ["sp-mixed-5000.saturated"]
EARLIER = set(base.OLD) | set(base.NEW) | set(base.PR28)


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    """As test_perfbench_spans.py's: the benchmark after a later PR has
    added a row and two per-layer entries (tools/later_pr_tree.py)."""
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later32")), "checkout"))


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_four_after_the_28_that_were_there(
        later, later_root):
    """Held by name and by the place PR 32 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[28:32] == list(PR32)
    if later:
        assert names[32:]                # the copy does hold entries added
    for name, (layer, source) in PR32.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "better": "lower", "source": source,
            "layer": layer, "moves": "pods_bound_per_s",
            "unit": "count" if name.startswith("mirror_rows") else "ms"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][:3] == ALL_CELLS
    for cell in ALL_CELLS:
        assert set(spec.cell(cell, root).readers()) >= set(PR32)


# ------------------------------------------------------- cycles by hand

def _cycle32(t, refilled=None):
    """``_cycle28`` with tensorize's 200 ms split as the program records
    it: delta-build 120, delta-apply 30, batch-build 40 (10 ms under no
    child); ``refilled``: (node rows, pod rows) the build says it rewrote,
    None for a program from before PR 32."""
    c = base._cycle28(t)
    build = next(s for s in c["spans"] if s["name"] == "delta-build")
    build["args"]["terms_kept"] = 0
    if refilled is not None:
        build["args"].update(
            node_rows_dirty=2000, node_rows_refilled=refilled[0],
            pod_rows_seen=4400, pod_rows_refilled=refilled[1])
    c["spans"] += [base._span("delta-apply", t + 0.2, t + 0.23),
                   base._span("batch-build", t + 0.23, t + 0.27)]
    return c


TWO32 = [_cycle32(0.0, (0, 1024)), _cycle32(1.0, (2, 1030))]
WANT32 = {
    "delta_build_ms_per_cycle.sat": 120.0,
    "delta_apply_ms_per_cycle.sat": 30.0,
    "batch_build_ms_per_cycle.sat": 40.0,
    "mirror_rows_refilled_per_cycle.sat": 1028.0,     # 1,024 and 1,032
}


@pytest.mark.parametrize("name", sorted(PR32))
def test_a_pr32_reader_on_cycles_worked_out_by_hand(name):
    assert set(WANT32) == set(PR32)
    assert base._reader(name)(base._ctx(TWO32)) == pytest.approx(
        WANT32[name], rel=1e-9)
    # a cycle that ran no delta build (a resync, a chained cycle) is left
    # out of the mean, not counted as 0
    bare = base._cycle(2.0)
    bare["spans"] = [sp for sp in bare["spans"]
                     if sp["name"] != "delta-build"]
    assert base._reader(name)(base._ctx(TWO32 + [bare])) == pytest.approx(
        WANT32[name], rel=1e-9)
    assert sum(WANT32[n] for n in sorted(PR32)[:3]) <= base.WANT28[
        "tensorize_ms_per_cycle.sat"]


@pytest.mark.parametrize("name", sorted(PR32))
def test_a_pr32_reader_finds_nothing_where_there_is_nothing_to_read(name):
    """The three spans are there since PR 5, so their readers read the
    parent of PR 32 too; its delta-build does not say what it refilled,
    and the counter's reader then returns None: never 0, never raises."""
    parent = [_cycle32(0.0), _cycle32(1.0)]
    if name == "mirror_rows_refilled_per_cycle.sat":
        assert base._reader(name)(base._ctx(parent)) is None
        # one build of a run that does not say: nothing is summed
        assert base._reader(name)(base._ctx(TWO32 + parent[:1])) is None
    else:
        assert base._reader(name)(base._ctx(parent)) == pytest.approx(
            WANT32[name], rel=1e-9)
    old = {"seq": 1, "t0": 0.0, "t1": 1.0, "meta": {}, "events": [],
           "spans": [base._span("dispatch", 0.3, 0.4),
                     base._span("packed-readback", 0.4, 0.45,
                                device_wait_s=0.04),
                     base._span("commit", 0.45, 0.95)]}
    assert base._reader(name)(base._ctx([old])) is None
    assert base._reader(name)(base._ctx([])) is None


# ---------------------------------------------- a whole traced run, toy

def _list_the_toy_cell_for_every_metric(root):
    """``test_perfbench_spans.py``'s, with the four of PR 32 too."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in EARLIER or m["name"] in PR32:
            m["workloads"].append("toy-anti-96.closed")
    with open(path, "w") as f:
        json.dump(bench, f)


@pytest.fixture(scope="module")
def toy_traced(tmp_path_factory):
    """The toy anti-affinity cell listed for the four of PR 32 beside the
    metrics they split, run traced through drive.run_cell on the CPU."""
    from kubetpu.utils import sanitize
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy32")))
    _list_the_toy_cell_for_every_metric(root)
    cell = spec.cell("toy-anti-96.closed", root)
    said, kept = [], {}

    def keep(**kw):              # what run_cell hands the readers as ctx
        kept.update(kw)
        return SimpleNamespace(**kw)
    armed = list(sanitize._watchdogs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(drive, "SimpleNamespace", keep)
        try:
            res = drive.run_cell(cell, seed=2 ** 31 + 32, seconds=3.0,
                                 trace=True, require_tpu=False,
                                 out=said.append)
        finally:
            # a run never takes its compile watchdog off; a test process
            # lives on
            for wd in list(sanitize._watchdogs):
                if wd not in armed:
                    sanitize.uninstall_compile_watchdog(wd)
    return res, kept, "\n".join(said)


def test_a_traced_toy_run_fills_the_four_of_pr32(toy_traced):
    res, ctx, said = toy_traced
    assert res["correct"] is True, said
    got = res["metrics"]
    assert set(got) >= set(PR32)
    # tensorize's children stay inside it, and the build says what it
    # refilled (every pod of the toy arrives once)
    for name in PR32:
        assert got[name]["value"] > 0, name
    assert (got["delta_build_ms_per_cycle.sat"]["value"]
            + got["delta_apply_ms_per_cycle.sat"]["value"]
            + got["batch_build_ms_per_cycle.sat"]["value"]) \
        <= got["tensorize_ms_per_cycle.sat"]["value"] * 1.001


def test_the_toy_runs_builds_say_what_they_refilled(toy_traced):
    """The counter the reader sums is the program's own: every delta build
    of the run says all four args, rewrites no node part (no Node is set
    again in a run) and at most the pod rows it walked."""
    res, ctx, said = toy_traced
    builds = [s for c in ctx["cycles"] for s in c["spans"]
              if s["name"] == "delta-build"]
    assert builds
    for s in builds:
        a = s["args"]
        assert a["node_rows_refilled"] == 0
        assert 0 <= a["pod_rows_refilled"] <= a["pod_rows_seen"]
        assert a["node_rows_dirty"] >= 1
    # (every pod of the toy excludes every other, so a dirty node holds
    # the one pod that arrived: seen and refilled are equal here; the
    # counter test in tests/test_delta_terms_trace.py has residents)
    assert sum(s["args"]["pod_rows_refilled"] for s in builds) > 0
