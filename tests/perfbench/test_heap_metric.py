"""The reader PR 39 added (perfbench/metrics/heap_handoffs_per_cycle.sat)
on cycle records worked out by hand, on the recorded v5e capture's cycles
(a program from before PR 39, which does not say ``heap_handoffs``), in a
traced toy run of the whole harness, and its entry in BENCHMARK.json.  A
file of its own, beside test_perfbench_spans.py whose helpers it borrows:
a PR that adds a metric adds files to the benchmark and edits none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import drive, spec
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
NAME = "heap_handoffs_per_cycle.sat"
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated"]
FIRST = 52          # the per-layer metrics that were there before


def _cycle39(t, handoffs=None, frozen=150000, swept=0):
    """``test_perfbench_spans._cycle`` as a program since PR 39 records
    it while a heap policy serves; handoffs None: a program that does not
    say."""
    c = base._cycle(t)
    c["meta"] = {"pods": 4, "auction_rounds": 1}
    if handoffs is not None:
        c["meta"].update(heap_handoffs=handoffs, heap_frozen=frozen)
        if swept:
            c["meta"]["heap_sweep_collected"] = swept
    return c


def _read(cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[NAME](
        SimpleNamespace(cycles=cycles))


@pytest.mark.parametrize("every", [1, 4, 16])
def test_the_reader_reads_one_over_k(every):
    cycles = [_cycle39(float(i), int(i % every == every - 1),
                       frozen=150000 + 6000 * (i // every))
              for i in range(48)]
    assert _read(cycles) == pytest.approx(1.0 / every)
    for cell in CELLS:                              # every cell reads it
        assert _read(cycles, of=cell) == pytest.approx(1.0 / every)


def test_the_reader_on_cycles_worked_out_by_hand():
    # a window in which no hand-off fell is a reading of 0, not a gap
    assert _read([_cycle39(0.0, 0), _cycle39(1.0, 0)]) == 0.0
    # a sweep ends in a hand-off and counts as one
    assert _read([_cycle39(0.0, 0), _cycle39(1.0, 1, swept=3)]) == 0.5
    # the first cycle after arming can carry two (start-up's neighbours)
    assert _read([_cycle39(0.0, 2), _cycle39(1.0, 0)]) == 1.0
    assert _read([]) is None


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    parent = [_cycle39(0.0), _cycle39(1.0)]
    assert _read(parent) is None
    # the cycles that say are averaged among themselves: a recorder
    # armed before run() holds a first cycle from before the policy
    assert _read(parent[:1] + [_cycle39(2.0, 1), _cycle39(3.0, 0)]) == 0.5
    assert _read(base.TWO) is None                  # PR 26's hand cycles
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and not any(k.startswith("heap_")
                                for c in recorded for k in c["meta"])
    assert _read(recorded) is None


def test_a_traced_toy_run_reports_the_hand_offs(tmp_path, monkeypatch):
    """The toy anti-affinity cell, listed for the metric, through the
    whole of drive.run_cell on the CPU: the scheduler it runs hands off
    every HANDOFF_EVERY-th cycle, and the reader says so."""
    from kubetpu.utils import heap as uheap
    from kubetpu.utils import sanitize
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 2)
    root = perfbench_toy.make_root(str(tmp_path))
    base._list_the_toy_cell_for_every_metric(root)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for m in bench["per_layer"]:
        if m["name"] == NAME:
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
        res = drive.run_cell(cell, seed=2 ** 31 + 39, seconds=3.0,
                             trace=True, require_tpu=False,
                             out=said.append)
    finally:
        for wd in list(sanitize._watchdogs):
            if wd not in armed:
                sanitize.uninstall_compile_watchdog(wd)
    assert res["correct"] is True, "\n".join(said)
    got = res["metrics"][NAME]
    assert got["unit"] == "count"
    # whole cycles between two hand-offs: the window's share is 1/2 but
    # for the cycles at its two ends
    n = len(kept["cycles"])
    assert n >= 4 and abs(got["value"] - 0.5) <= 1.0 / n + 1e-9
    metas = [c["meta"] for c in kept["cycles"]]
    assert all(m["heap_handoffs"] in (0, 1) for m in metas)
    assert all(m["heap_frozen"] > 0 for m in metas)
    # close() gave the heap back; what the harness froze went with it
    import gc
    assert gc.get_freeze_count() == 0


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later39")), "checkout"))


@pytest.mark.parametrize("later", [False, True],
                         ids=["as-committed", "with-entries-added"])
def test_benchmark_json_names_the_metric_after_the_52_that_were_there(
        later, later_root):
    """Held by name and by the place PR 39 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[FIRST] == NAME and names.count(NAME) == 1
    m = bench["per_layer"][FIRST]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "interpreter",
        "moves": "pods_bound_per_s"}
    assert m["workloads"][:5] == CELLS
    for cell in CELLS:
        assert NAME in spec.cell(cell, root).readers()
    assert [w["name"] for w in bench["workloads"]][:5] == CELLS
