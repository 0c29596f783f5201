"""The reader PR 50 added (perfbench/metrics/commit_batched_pct.sat): on
cycle records worked out by hand, on records of a program whose commit
loop walks a pod at a time and does not say (the parent: None, never 0,
never raises), in a traced toy run of the whole harness -- where the
``commit`` span still carries its nine sums and they still add up -- and
its entry in BENCHMARK.json.  A file of its own, beside
test_perfbench_spans.py whose helpers it borrows: a PR that changes the
program adds files to the benchmark and edits none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import drive, spec

REPO = perfbench_toy.REPO
NAME = "commit_batched_pct.sat"
FIRST = 70          # the per-layer metrics that were there before
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated",
         "sp-prefspread-5000.saturated", "sp-podaffinity-5000.saturated",
         "sp-nodeaffinity-5000.saturated"]
SIX = base.SIX


def _cycle50(t, pods=1024, batched=None):
    """PR 26's hand cycle with ``pods`` placed pods on its ``commit``
    span; ``batched``: how many of them a run committed, as a program
    since PR 50 says it, None for one whose loop walks a pod at a time."""
    c = base._cycle(t)
    (commit,) = [s for s in c["spans"] if s["name"] == "commit"]
    commit["args"]["pods"] = pods
    if batched is not None:
        commit["args"]["batched"] = batched
    return c


def _read(cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[NAME](base._ctx(cycles, root=root))


def test_benchmark_json_names_it_for_all_nine_cells():
    """Held by name and by the place PR 50 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    bench = spec.load_benchmark(REPO)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[FIRST] == NAME and names.count(NAME) == 1
    m = bench["per_layer"][FIRST]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "commit and bind",
        "moves": "pods_bound_per_s"}
    assert m["workloads"][:9] == CELLS      # a later PR's cell may follow
    for cell in CELLS:
        assert NAME in spec.cell(cell, REPO).readers()
    assert os.path.isfile(os.path.join(REPO, "perfbench", "metrics",
                                       NAME + ".py"))


def test_the_reader_on_cycles_worked_out_by_hand():
    assert _read([_cycle50(0.0, 1024, 1024)]) == 100.0
    assert _read([_cycle50(0.0, 1024, 0)]) == 0.0
    # over the window's placed pods, not a mean of the cycles' own shares
    # (a cycle of four pods weighs four pods)
    assert _read([_cycle50(0.0, 1024, 1024), _cycle50(1.0, 1024, 1021),
                  _cycle50(2.0, 4, 0)]) == pytest.approx(
                      100.0 * 2045 / 2052)
    for cell in CELLS:                              # every cell reads it
        assert _read([_cycle50(0.0, 4, 3)], of=cell) == 75.0
    # a cycle that placed nothing has no say, and alone gives no reading
    assert _read([_cycle50(0.0, 0, 0), _cycle50(1.0, 8, 2)]) == 25.0
    assert _read([_cycle50(0.0, 0, 0)]) is None


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    """The parent of PR 50 commits a pod at a time: its ``commit`` span
    holds ``pods`` and no ``batched``."""
    assert _read([]) is None
    assert _read([_cycle50(0.0), _cycle50(1.0)]) is None
    assert _read(base.TWO) is None                  # PR 26's hand cycles
    assert _read(base.TWO28) is None                # PR 28's
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and _read(recorded) is None
    old = {"seq": 1, "t0": 0.0, "t1": 1.0, "meta": {}, "events": [],
           "spans": [base._span("commit", 0.3, 0.4)]}
    assert _read([old]) is None
    # the cycles that say are read among themselves
    assert _read([_cycle50(0.0), _cycle50(1.0, 8, 8)]) == 100.0


def test_a_traced_toy_run_commits_its_pods_in_runs(tmp_path, monkeypatch):
    """The toy anti-affinity cell (plain pods but for their term: no
    volume, no host filter that cares, no Permit plugin), listed for the
    metric, through the whole of drive.run_cell on the CPU: every placed
    pod of every cycle rides a run, and the ``commit`` span keeps the
    sums the older readers take, adding up to ``loop_s``."""
    from kubetpu.utils import sanitize
    root = perfbench_toy.make_root(str(tmp_path))
    base._list_the_toy_cell_for_every_metric(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in (NAME, "lane_batched_pct.sat"):
            m["workloads"].append("toy-anti-96.closed")
    with open(path, "w") as f:
        json.dump(bench, f)
    cell = spec.cell("toy-anti-96.closed", root)
    said, kept = [], {}

    def keep(**kw):              # what run_cell hands the readers as ctx
        kept.update(kw)
        return SimpleNamespace(**kw)
    monkeypatch.setattr(drive, "SimpleNamespace", keep)
    armed = list(sanitize._watchdogs)
    try:
        res = drive.run_cell(cell, seed=2 ** 31 + 50, seconds=3.0,
                             trace=True, require_tpu=False,
                             out=said.append)
    finally:
        for wd in list(sanitize._watchdogs):
            if wd not in armed:
                sanitize.uninstall_compile_watchdog(wd)
    assert res["correct"] is True, "\n".join(said)
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and got["value"] == 100.0
    commits = [s["args"] for c in kept["cycles"] for s in c["spans"]
               if s["name"] == "commit"]
    assert commits and sum(a["pods"] for a in commits) > 0
    under = 0
    for a in commits:
        assert a["batched"] == a["pods"]
        # the nine sums and the hand-over's three, as ever
        assert set(a) >= set(SIX) | {"pods", "loop_s", "loop_cpu_s",
                                     "bind_jobs", "binds_pooled"}
        assert all(a[k] >= 0.0 for k in SIX)
        # no pod re-checked, reserved or permitted: a class walk a cycle
        assert a["recheck_s"] == 0.0
        assert sum(a[k] for k in SIX) <= a["loop_s"] + 1e-4
        under += sum(a[k] for k in SIX) < 0.9 * a["loop_s"]
        if a["pods"]:
            assert a["assume_s"] > 0.0 and a["submit_s"] > 0.0
            assert a["bind_jobs"] == 1 and a["binds_pooled"] == 0
    # the stamps cover the loop (a hiccup between the last of them and
    # the clock that closes loop_s may fall in a toy cycle of a run:
    # test_perfbench_spans._under_the_floor)
    assert under <= 1
    # and the older readers of the span still read it
    for name in ("commit_assume_ms_per_cycle.sat",
                 "commit_plugins_ms_per_cycle.sat",
                 "commit_submit_ms_per_cycle.sat",
                 "commit_ms_per_cycle.sat"):
        assert res["metrics"][name]["value"] is not None, name
    assert res["metrics"]["lane_batched_pct.sat"]["value"] == 100.0
    assert (res["metrics"]["commit_plugins_ms_per_cycle.sat"]["value"]
            < res["metrics"]["commit_assume_ms_per_cycle.sat"]["value"])
