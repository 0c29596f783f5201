"""The reader PR 43 added (perfbench/metrics/spread_late_admits_per_cycle.sat)
on cycle records worked out by hand, on a program that does not say (any
parent of PR 43), and its entry in BENCHMARK.json, as committed and with a
later PR's entries after it.  A file of its own, beside
test_perfbench_spans.py whose helpers it borrows: a PR that adds a metric
adds files to the benchmark and edits none."""

import os

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import spec
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
NAME = "spread_late_admits_per_cycle.sat"
CELL = "sp-topologyspread-5000.saturated"
FIRST = 60          # the per-layer metrics that were there before


def _cycle43(t, rounds=5, hard=1024, late=None):
    """A cycle as a program since PR 33 records it (``spread_constraints``:
    valid DoNotSchedule rows of the batch); late: the word PR 43 adds, None
    for a program that does not say."""
    c = base._cycle(t)
    c["meta"] = {"auction_rounds": rounds, "pods": 1024,
                 "spread_constraints": hard}
    if late is not None:
        c["meta"]["spread_late_admits"] = late
    return c


def _read(cycles, of=CELL, root=REPO):
    return spec.cell(of, root).readers()[NAME](base._ctx(cycles, root=root))


def test_the_reader_on_cycles_worked_out_by_hand():
    assert _read([_cycle43(0.0, late=900)]) == 900.0
    assert _read([_cycle43(0.0, late=1000), _cycle43(1.0, late=0),
                  _cycle43(2.0, late=500)]) == 500.0
    # a cycle whose batch held no hard constraint has no word and no say;
    # one that ran no auction neither
    plain = _cycle43(1.0, hard=0)
    idle = _cycle43(2.0, rounds=0)
    assert _read([_cycle43(0.0, late=800), plain, idle]) == 800.0
    assert _read([plain, idle]) is None


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    parent = [_cycle43(0.0), _cycle43(1.0)]
    assert _read(parent) is None
    # one cycle of a window not saying: no mean over the rest
    assert _read([_cycle43(0.0, late=7)] + parent[:1]) is None
    for cycles in ([], [base._cycle(0.0)]):
        assert _read(cycles) is None


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later43")), "checkout"))


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_metric(later, later_root):
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    m = bench["per_layer"][FIRST]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device programs",
        "moves": "pods_bound_per_s"}
    # the one cell whose batches hold a DoNotSchedule constraint; a later
    # PR's cell may list itself after it
    assert m["workloads"][0] == CELL
    if not later:
        assert m["workloads"] == [CELL]
    assert [x["name"] for x in bench["per_layer"]].count(NAME) == 1
    assert NAME in spec.cell(CELL, root).readers()
    for other in ("sp-basic-5000.saturated", "sp-prefspread-5000.saturated"):
        assert NAME not in spec.cell(other, root).readers()
    # the reader's file is the whole of what this PR adds to the benchmark
    path = os.path.join(root, "perfbench", "metrics", NAME + ".py")
    assert os.path.isfile(path)
    assert open(path).read().startswith('"""device programs: ')
