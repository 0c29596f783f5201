"""The seven readers PR 51 added (perfbench/lib/teardown.py and their
metric files) on a cycle record worked out by hand, on records of a
program that does not say (the parent: None, never 0, never raises), in a
traced toy run of the whole harness -- where the ``teardown`` span's
extent is ``pop.teardown_s`` and the parts cover the pop -- their entries
in BENCHMARK.json, and the health pass of
perfbench/tools/teardown_report.py.  A file of its own, beside
test_perfbench_spans.py whose helpers it borrows: a PR that changes the
program adds files to the benchmark and edits none."""

import copy
import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
import test_perfbench_spans_pr38 as p38
from perfbench.lib import drive, spec
from perfbench.tools import teardown_report

REPO = perfbench_toy.REPO
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated",
         "sp-prefspread-5000.saturated", "sp-podaffinity-5000.saturated",
         "sp-nodeaffinity-5000.saturated"]
LANE = "binder-lane"
# name -> source, in the order they were appended
ENTRIES = {
    "teardown_serving_cpu_ms_per_cycle.sat": "program_span",
    "teardown_lane_cpu_ms_per_cycle.sat": "program_counter",
    "teardown_other_threads_cpu_ms_per_cycle.sat": "program_counter",
    "teardown_release_ms_per_cycle.sat": "program_span",
    "heap_boundary_ms_per_cycle.sat": "program_span",
    "pop_queue_ms_per_cycle.sat": "program_span",
    "pop_group_ms_per_cycle.sat": "program_span",
}
FIRST = 71          # the per-layer metrics that were there before


def _cycle51(t, scale=1.0, release=True, threads=True):
    """PR 38's hand cycle as a program since PR 51 records it.  Its
    ``pop`` (40 ms to t, 10 of them waiting) opens with a teardown of
    20 ms x ``scale``: the serving thread ran 4 of them, the lane 9, the
    client 5 and a pool thread 1 (so for 1 nobody ran); the loop dropped
    the outcomes 12 ms in, the heap boundary took 5 from 14 ms in; then
    ``pop_batch`` 13 ms (10 of them the wait) and the grouping 6."""
    c = p38._cycle38(t)
    (pop,) = [s for s in c["spans"] if s["name"] == "pop"]
    k = scale
    pop["args"].update(teardown_s=0.02 * k, queue_s=0.013, group_s=0.006)
    t0 = pop["t0"]
    td = dict(base._span("teardown", t0, t0 + 0.02 * k, cpu_s=0.004 * k,
                         read_s=0.00004),
              id=30, parent=pop["id"])
    if threads:
        td["args"]["thread_cpu_s"] = {
            "serving": 0.0042 * k, LANE: 0.009 * k,
            "perfbench-client": 0.005 * k, "binder_pool": 0.001 * k}
    c["spans"].append(td)
    if release:
        c["spans"].append(dict(
            base._span("teardown-release", t0, t0 + 0.012 * k,
                       cpu_s=0.001, outcomes=4), id=31, parent=30))
    c["spans"].append(dict(
        base._span("heap-boundary", t0 + 0.014 * k, t0 + 0.019 * k,
                   cpu_s=0.003 * k, gc_s=0.002 * k, handoff=1, sweep=0),
        id=32, parent=30))
    return c


TWO51 = [_cycle51(0.0), _cycle51(1.0, scale=0.5)]
WANT = {    # means of a cycle at scale 1 and one at 0.5
    "teardown_serving_cpu_ms_per_cycle.sat": 3.0,
    "teardown_lane_cpu_ms_per_cycle.sat": 6.75,
    "teardown_other_threads_cpu_ms_per_cycle.sat": 4.5,
    "teardown_release_ms_per_cycle.sat": 9.0,
    "heap_boundary_ms_per_cycle.sat": 3.75,
    "pop_queue_ms_per_cycle.sat": 3.0,           # 13 less 10 of waiting
    "pop_group_ms_per_cycle.sat": 6.0,
}


def _read(name, cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[name](base._ctx(cycles, root=root))


def test_benchmark_json_names_the_seven_for_all_nine_cells():
    """Held by name and by the place PR 51 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    bench = spec.load_benchmark(REPO)
    got = bench["per_layer"][FIRST:FIRST + len(ENTRIES)]
    assert [m["name"] for m in got] == list(ENTRIES)
    for m in got:
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": m["name"], "unit": "ms", "better": "lower",
            "source": ENTRIES[m["name"]], "layer": "queue",
            "moves": "pods_bound_per_s"}
        assert m["workloads"][:9] == CELLS      # a later PR's cell may follow
        for cell in CELLS:
            assert m["name"] in spec.cell(cell, REPO).readers()
        assert os.path.isfile(os.path.join(REPO, "perfbench", "metrics",
                                           m["name"] + ".py"))
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.count(n) == 1 for n in ENTRIES)
    # the layer's older metrics are where they were
    for old in ("pop_ms_per_cycle.sat", "pop_teardown_ms_per_cycle.sat",
                "queue_empty_wait_ms_per_cycle.sat"):
        assert names.index(old) < FIRST


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_reader_on_cycles_worked_out_by_hand(name):
    assert set(WANT) == set(ENTRIES)
    assert _read(name, TWO51) == pytest.approx(WANT[name], rel=1e-9)
    for cell in CELLS[1:]:                          # every cell reads it
        assert _read(name, TWO51[:1], of=cell) == pytest.approx(
            _read(name, TWO51[:1]), rel=1e-9)
    # a cycle that does not say is left out of the mean, not counted as 0
    assert _read(name, TWO51 + [p38._cycle38(2.0)]) == pytest.approx(
        WANT[name], rel=1e-9)
    # what the older readers of the span take is where it was
    assert _read("pop_ms_per_cycle.sat", TWO51) == pytest.approx(30.0)
    assert _read("pop_teardown_ms_per_cycle.sat", TWO51) == pytest.approx(
        15.0)
    assert _read("queue_empty_wait_ms_per_cycle.sat", TWO51) == \
        pytest.approx(10.0)


def test_the_three_cpu_readings_leave_the_time_nobody_ran():
    """``teardown_s`` less the three is a difference of metrics on one
    line: 20 - 4 - 9 - 6 = 1 ms in the first hand cycle (the serving
    thread's own share by ``cpu_s``, its entry of ``thread_cpu_s`` is
    left out of the other threads')."""
    one = TWO51[:1]
    left = _read("pop_teardown_ms_per_cycle.sat", one) - sum(
        _read(n, one) for n in list(ENTRIES)[:3])
    assert left == pytest.approx(1.0)
    # a thread the teardown does not name did not run in it: 0, not None
    quiet = _cycle51(0.0)
    (td,) = [s for s in quiet["spans"] if s["name"] == "teardown"]
    td["args"]["thread_cpu_s"] = {"serving": 0.0042}
    assert _read("teardown_lane_cpu_ms_per_cycle.sat", [quiet]) == 0.0
    assert _read("teardown_other_threads_cpu_ms_per_cycle.sat",
                 [quiet]) == 0.0


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_program_that_does_not_say_reads_none_and_nothing_raises(name):
    """The parent of PR 51: ``pop`` with ``teardown_s`` and ``wait_s``
    and nothing inside."""
    assert _read(name, []) is None
    assert _read(name, [p38._cycle38(0.0), p38._cycle38(1.0)]) is None
    assert _read(name, base.TWO) is None            # PR 26's hand cycles
    assert _read(name, base.TWO28) is None          # PR 28's: teardown_s
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and _read(name, recorded) is None
    old = {"seq": 1, "t0": 0.0, "t1": 1.0, "meta": {}, "events": [],
           "spans": [base._span("commit", 0.3, 0.4)]}
    assert _read(name, [old]) is None


def test_a_caller_that_kept_the_outcomes_and_a_platform_without_the_clock():
    """No ``teardown-release`` (``schedule_pending()`` driven by hand) and
    no ``thread_cpu_s`` (no per-thread CPU clock): those readers say
    None, the others read on."""
    kept = [_cycle51(0.0, release=False, threads=False)]
    assert _read("teardown_release_ms_per_cycle.sat", kept) is None
    assert _read("teardown_lane_cpu_ms_per_cycle.sat", kept) is None
    assert _read("teardown_other_threads_cpu_ms_per_cycle.sat",
                 kept) is None
    assert _read("teardown_serving_cpu_ms_per_cycle.sat", kept) == \
        pytest.approx(4.0)
    assert _read("heap_boundary_ms_per_cycle.sat", kept) == \
        pytest.approx(5.0)


def test_the_report_on_the_hand_cycles():
    rep = teardown_report.structure(copy.deepcopy(TWO51)
                                    + [p38._cycle38(2.0)])
    assert rep["cycles"] == 3 and rep["teardowns"] == 2
    assert rep["complete_share"] == pytest.approx(2 / 3, abs=1e-4)
    assert rep["teardown_ms"] == rep["teardown_s_arg_ms"] == 15.0
    assert rep["pop_less_wait_ms"] == 30.0
    assert rep["teardown_thread_cpu_ms_by_name"] == {
        "(serving thread)": 3.15, "binder-lane": 6.75, "binder_pool": 0.75,
        "perfbench-client": 3.75}
    assert rep["teardown_no_thread_ran_ms"] == pytest.approx(0.6)
    assert (rep["release_ms"], rep["heap_boundary_ms"]) == (9.0, 3.75)
    assert (rep["heap_handoffs"], rep["heap_sweeps"]) == (2, 0)
    assert (rep["queue_less_wait_ms"], rep["group_ms"]) == (3.0, 6.0)
    # 20 + 3 + 6 of the pop's 30 less its wait, and 10 + 3 + 6 of 30
    assert rep["parts_cover_pop_min_mean"] == [0.6333, 0.8]
    assert rep["thread_clock_read_us_mean_max"] == [40.0, 40.0]
    assert set(rep["violations"].values()) == {0}
    # a program that does not say: nothing counted, nothing raised
    assert teardown_report.structure(base.TWO28) == {"cycles": 2,
                                                     "teardowns": 0}
    # a child that overruns its teardown, CPU beyond an extent, and an
    # extent that is not ``teardown_s`` are each counted
    bad = _cycle51(0.0)
    by = {s["name"]: s for s in bad["spans"]}
    by["heap-boundary"]["t1"] = by["teardown"]["t1"] + 0.01
    by["teardown-release"]["args"]["cpu_s"] = 0.05
    by["pop"]["args"]["teardown_s"] = 0.03
    got = teardown_report.structure([bad])["violations"]
    assert (got["child_outside_teardown"], got["cpu_over_extent"],
            got["extent_off_teardown_s"]) == (1, 1, 1)


def test_a_traced_toy_run_lights_the_pop_from_the_inside(tmp_path,
                                                         monkeypatch):
    """The toy anti-affinity cell, listed for the seven, through the whole
    of drive.run_cell on the CPU: every one prints a number, the
    ``teardown`` span's extent is ``teardown_s``, its children lie inside
    it, and the older readers of ``pop`` read what they read."""
    from kubetpu.utils import sanitize
    root = perfbench_toy.make_root(str(tmp_path))
    base._list_the_toy_cell_for_every_metric(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] in ENTRIES:
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
        res = drive.run_cell(cell, seed=2 ** 31 + 51, seconds=3.0,
                             trace=True, require_tpu=False,
                             out=said.append)
    finally:
        for wd in list(sanitize._watchdogs):
            if wd not in armed:
                sanitize.uninstall_compile_watchdog(wd)
    assert res["correct"] is True, "\n".join(said)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in ENTRIES:
        assert res["metrics"][name]["unit"] == "ms"
        assert got[name] >= 0.0, name
    for name in ("teardown_serving_cpu_ms_per_cycle.sat",
                 "teardown_release_ms_per_cycle.sat",
                 "heap_boundary_ms_per_cycle.sat",
                 "pop_group_ms_per_cycle.sat"):
        assert got[name] > 0.0, name
    # inside what they split
    assert (got["teardown_release_ms_per_cycle.sat"]
            + got["heap_boundary_ms_per_cycle.sat"]
            <= got["pop_teardown_ms_per_cycle.sat"] * 1.001)
    assert (got["teardown_serving_cpu_ms_per_cycle.sat"]
            <= got["pop_teardown_ms_per_cycle.sat"] * 1.001 + 0.2)
    rep = teardown_report.structure(kept["cycles"])
    assert rep["teardowns"] >= 3
    assert set(rep["violations"].values()) == {0}, rep
    # ONE extent under two names
    assert rep["teardown_ms"] == pytest.approx(rep["teardown_s_arg_ms"],
                                               abs=0.01)
    assert rep["heap_handoffs"] >= 1 and rep["heap_sweeps"] == 0
    assert rep["release_outcomes_mean"] > 0
