"""The reader PR 47 added (perfbench/metrics/term_rows_written_per_cycle.sat):
on cycle records worked out by hand, on the record of a program that
rebuilds the term tables whole and does not say what it wrote, and beside
``term_rows_rebuilt_per_cycle.sat``, which reads the same span and now
gives the tables' live rows.  A file of its own, beside
test_perfbench_spans.py whose helpers it borrows: a PR that changes the
program adds files to the benchmark and edits none."""

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.lib import spec

REPO = perfbench_toy.REPO
NAME = "term_rows_written_per_cycle.sat"
LIVE = "term_rows_rebuilt_per_cycle.sat"
CELLS = ["sp-antiaffinity-5000.saturated", "sp-mixed-5000.saturated",
         "sp-prefaffinity-5000.saturated", "sp-podaffinity-5000.saturated"]


def _reader(name, cell=CELLS[-1]):
    return spec.cell(cell, REPO).readers()[name]


def test_benchmark_json_names_it_for_the_four_cells_with_term_rows():
    bench = spec.load_benchmark(REPO)
    m, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_span", "layer": "prepare",
        "moves": "pods_bound_per_s"}
    # a later PR's cell may list itself
    assert m["workloads"][:4] == CELLS
    for cell in CELLS:
        assert NAME in spec.cell(cell, REPO).readers()
    assert NAME not in spec.cell("sp-basic-5000.saturated", REPO).readers()


def _cycle47(t, written=None, live=(0, 6200)):
    """A cycle whose delta build held a term update of 10 ms: ``written``
    the rows it says it wrote, None for a program from before PR 47 (it
    recompiled ``live`` rows and says those alone)."""
    c = base._cycle(t, terms_ms=10.0)
    terms = next(s for s in c["spans"] if s["name"] == "delta-terms")
    terms["args"].update(filter_rows=live[0], score_rows=live[1])
    if written is not None:
        terms["args"].update(rows_written=written, rows_free=3, wholesale=0)
    return c


def test_the_reader_on_cycles_worked_out_by_hand():
    two = [_cycle47(0.0, 2048), _cycle47(1.0, 2040)]
    assert _reader(NAME)(base._ctx(two)) == pytest.approx(2044.0)
    # the live rows are another thing, read off the same span
    assert _reader(LIVE)(base._ctx(two)) == pytest.approx(6200.0)
    # a cycle whose build met no owner coming or going counts 0 ...
    quiet = base._cycle(2.0)
    assert _reader(NAME)(base._ctx(two + [quiet])) == pytest.approx(
        4088.0 / 3)
    # ... and one that ran no delta build (a resync, a chained cycle) is
    # left out of the mean
    bare = base._cycle(3.0)
    bare["spans"] = [s for s in bare["spans"] if s["name"] != "delta-build"]
    assert _reader(NAME)(base._ctx(two + [bare])) == pytest.approx(2044.0)


def test_the_reader_finds_nothing_where_there_is_nothing_to_read():
    """The parent of PR 47 rebuilds both tables from the owner list and
    says what it rebuilt, not what it wrote: None, never 0, never raises.
    Where no term is ever dirty (``sp-mixed-5000``) both programs carry no
    ``delta-terms`` span and both read 0."""
    parent = [_cycle47(0.0), _cycle47(1.0)]
    assert _reader(NAME)(base._ctx(parent)) is None
    assert _reader(NAME)(base._ctx(parent[:1] + [_cycle47(1.0, 7)])) is None
    kept = [base._cycle(0.0), base._cycle(1.0)]
    assert _reader(NAME, "sp-mixed-5000.saturated")(base._ctx(kept)) == 0.0
    assert _reader(NAME)(base._ctx([])) is None
    old = {"seq": 1, "t0": 0.0, "t1": 1.0, "meta": {}, "events": [],
           "spans": [base._span("dispatch", 0.3, 0.4)]}
    assert _reader(NAME)(base._ctx([old])) is None
