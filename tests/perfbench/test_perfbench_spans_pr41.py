"""The reader PR 41 added (perfbench/metrics/lane_batched_pct.sat) on
cycle records worked out by hand, on the recorded v5e capture's cycles
and PR 38's hand cycle (programs from before PR 41, whose ``bind-job``
span does not say ``batched``), in a traced toy run of the whole harness,
and its entry in BENCHMARK.json.  A file of its own, beside
test_perfbench_spans.py and test_perfbench_spans_pr38.py whose helpers it
borrows: a PR that adds a metric adds files to the benchmark and edits
none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
import test_perfbench_spans_pr38 as pr38
from perfbench.lib import drive, spec
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
NAME = "lane_batched_pct.sat"
CELLS = pr38.CELLS + ["sp-prefaffinity-5000.saturated"]
FIRST = 56          # the per-layer metrics that were there before


def _cycle41(t, pods=4, batched=None):
    """PR 38's hand cycle as a program since PR 41 records it: the
    ``bind-job`` span says how many of its rows rode a batch; batched
    None: a program that does not say."""
    c = pr38._cycle38(t)
    (job,) = [s for s in c["spans"] if s["name"] == "bind-job"]
    job["args"]["pods"] = pods
    if batched is not None:
        job["args"]["batched"] = batched
    return c


def _read(cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[NAME](base._ctx(cycles, root=root))


def test_the_reader_on_cycles_worked_out_by_hand():
    assert _read([_cycle41(0.0, 4, 4)]) == 100.0
    assert _read([_cycle41(0.0, 4, 0)]) == 0.0
    # over the window's jobs: rows that rode a batch over rows, not a
    # mean of the jobs' own shares (a job of one row weighs one row)
    assert _read([_cycle41(0.0, 1024, 1024), _cycle41(2.0, 1024, 1021),
                  _cycle41(4.0, 1, 0)]) == pytest.approx(
                      100.0 * 2045 / 2049)
    for cell in CELLS:                              # every cell reads it
        assert _read([_cycle41(0.0, 4, 3)], of=cell) == 75.0
    # a cycle whose binds all went to the pool has no job and no say
    none = _cycle41(0.0, 4, 4)
    none["spans"] = [s for s in none["spans"] if s["name"] != "bind-job"]
    assert _read([none, _cycle41(2.0, 4, 2)]) == 50.0


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    assert _read([]) is None
    assert _read([_cycle41(0.0), _cycle41(2.0)]) is None    # PR 38's job
    assert _read(base.TWO) is None                  # PR 26's hand cycles
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and not any(s["name"] == "bind-job"
                                for c in recorded for s in c["spans"])
    assert _read(recorded) is None
    # the jobs that say are read among themselves
    assert _read([_cycle41(0.0), _cycle41(2.0, 4, 4)]) == 100.0


def test_a_traced_toy_run_rides_the_batch_whole(tmp_path, monkeypatch):
    """The toy anti-affinity cell, listed for the metric, through the
    whole of drive.run_cell on the CPU: no row of it waits on Permit or
    has a PreBind plugin of its own, so every row of every job rides its
    batch, and the job's own stamps keep PR 38's promises."""
    from kubetpu.utils import sanitize
    from perfbench.tools import interp_report
    root = perfbench_toy.make_root(str(tmp_path))
    base._list_the_toy_cell_for_every_metric(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] == NAME or m["name"] in pr38.ENTRIES:
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
        res = drive.run_cell(cell, seed=2 ** 31 + 41, seconds=3.0,
                             trace=True, require_tpu=False,
                             out=said.append)
    finally:
        for wd in list(sanitize._watchdogs):
            if wd not in armed:
                sanitize.uninstall_compile_watchdog(wd)
    assert res["correct"] is True, "\n".join(said)
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and got["value"] == 100.0
    jobs = [s for c in kept["cycles"] for s in c["spans"]
            if s["name"] == "bind-job"]
    assert jobs and all(s["args"]["batched"] == s["args"]["pods"] > 0
                        and s["args"]["pooled"] == 0 for s in jobs)
    # a batch's rows start together and end together, on the lane
    for c in kept["cycles"]:
        rows = [r for r in c["binds"] if r[2] > 0.0]
        assert len({(r[1], r[2], r[3]) for r in rows}) <= 1
    bad = dict(interp_report.structure(kept["cycles"])["violations"])
    bad.pop("phase_cpu_off_thread_cpu")     # a toy cycle: see pr38's test
    assert not any(bad.values()), bad


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later41")), "checkout"))


@pytest.mark.parametrize("later", [False, True],
                         ids=["as-committed", "with-entries-added"])
def test_benchmark_json_names_the_metric_after_the_56_that_were_there(
        later, later_root):
    """Held by name and by the place PR 41 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[FIRST] == NAME and names.count(NAME) == 1
    m = bench["per_layer"][FIRST]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "commit and bind",
        "moves": "pods_bound_per_s"}
    assert m["workloads"][:6] == CELLS
    for cell in CELLS:
        assert NAME in spec.cell(cell, root).readers()
    assert [w["name"] for w in bench["workloads"]][:6] == CELLS
