"""The reader PR 37 added (perfbench/metrics/
auction_term_sets_live_per_cycle.sat) on cycle records worked out by hand,
on the recorded v5e capture's cycles (a program from before PR 37, which
does not say ``term_sets_live``), and its entry in BENCHMARK.json.  A file
of its own, beside test_perfbench_spans.py whose helpers it borrows: a PR
that adds a metric adds files to the benchmark and edits none."""

import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import spec
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
NAME = "auction_term_sets_live_per_cycle.sat"
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated"]


def _cycle37(t, rounds, live=None):
    """``test_perfbench_spans._cycle`` with an auction of ``rounds``
    rounds; ``live``: the batch's live term sets, None for a program from
    before PR 37."""
    c = base._cycle(t)
    c["meta"] = {"pods": 4, "auction_rounds": rounds}
    if live is not None:
        c["meta"]["term_sets_live"] = list(live)
    return c


def _read(cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[NAME](
        SimpleNamespace(cycles=cycles))


def test_the_reader_on_cycles_worked_out_by_hand():
    assert _read([_cycle37(0.0, 1, [])]) == 0.0      # a reading, not a gap
    assert _read([_cycle37(0.0, 3, ["raa"])]) == 1.0
    mixed = [_cycle37(0.0, 1, []), _cycle37(1.0, 9, ["raa", "spread"]),
             _cycle37(2.0, 2, ["spread"])]
    assert _read(mixed) == 1.0
    # a cycle that ran no auction is left out, not counted as 0 sets
    idle = _cycle37(3.0, 0, [])
    assert _read(mixed + [idle]) == 1.0
    assert _read([idle]) is None and _read([]) is None
    for cell in CELLS:                               # every cell reads it
        assert _read(mixed, of=cell) == 1.0


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    parent = [_cycle37(0.0, 1), _cycle37(1.0, 2)]
    assert _read(parent) is None
    # one cycle of a run that does not say: nothing is averaged
    assert _read(parent[:1] + [_cycle37(2.0, 1, ["spread"])]) is None
    # the recorded v5e capture's cycles are such a program's
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and all("term_sets_live" not in c["meta"]
                            for c in recorded)
    assert _read(recorded) is None
    for c in recorded:          # ... whether or not they ran an auction
        c["meta"]["auction_rounds"] = 1
    assert _read(recorded) is None


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later37")), "checkout"))


@pytest.mark.parametrize("later", [False, True],
                         ids=["as-committed", "with-entries-added"])
def test_benchmark_json_names_the_metric_after_the_43_that_were_there(
        later, later_root):
    """Held by name and by the place PR 37 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    assert names[43] == NAME and names.count(NAME) == 1
    m = bench["per_layer"][43]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_span", "layer": "device programs",
        "moves": "pods_bound_per_s"}
    assert m["workloads"][:5] == CELLS
    for cell in CELLS:
        assert NAME in spec.cell(cell, root).readers()
    assert [w["name"] for w in bench["workloads"]][:5] == CELLS
