"""The four readers PR 33 added (perfbench/metrics/auction_rounds_per_cycle.sat,
auction_admits_per_round.sat, spread_constraints_per_cycle.sat and
auction_spread_roofline) on cycle records worked out by hand and on a record
of a program that does not say ``spread_constraints``; the count of
``perfbench/kernels/spread.py`` on a hand-worked shape; the entries of
BENCHMARK.json.  A file of its own, beside test_perfbench_spans.py whose
helpers it borrows: a PR that adds a row adds files to the benchmark and
edits none."""

import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
from perfbench.kernels import auction, peaks, spread
from perfbench.lib import spec, world
from perfbench.tools import later_pr_tree

REPO = perfbench_toy.REPO
CELL = "sp-topologyspread-5000.saturated"
OLD_CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
             "sp-mixed-5000.saturated"]
# name -> (unit, better, source, layer, the cells it lists first)
PR33 = {
    "auction_rounds_per_cycle.sat": ("count", "lower", "program_span",
                                     "device programs", OLD_CELLS + [CELL]),
    "auction_admits_per_round.sat": ("count", "higher", "program_span",
                                     "device programs", OLD_CELLS + [CELL]),
    "spread_constraints_per_cycle.sat": ("count", "lower", "program_span",
                                         "prepare", [CELL]),
    "auction_spread_roofline": ("%", "higher", "device_trace",
                                "device programs", [CELL]),
}


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    """The benchmark after a later PR has added a row and two per-layer
    entries (tools/later_pr_tree.py)."""
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later33")), "checkout"))


@pytest.mark.parametrize("later", [False, True], ids=["as-committed",
                                                      "with-entries-added"])
def test_benchmark_json_names_the_four_after_the_32_that_were_there(
        later, later_root):
    """Held by name and by the place PR 33 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    names = [m["name"] for m in bench["per_layer"]]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert names[32:36] == list(PR33)
    if later:
        assert names[36:]                # the copy does hold entries added
    for name, (unit, better, source, layer, cells) in PR33.items():
        m = by_name[name]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "pods_bound_per_s"}
        # a later PR's cell may list itself for a metric that is there
        assert m["workloads"][:len(cells)] == cells
    assert set(spec.cell(CELL, root).readers()) >= set(PR33)
    for cell in OLD_CELLS:
        got = set(spec.cell(cell, root).readers())
        assert got >= set(list(PR33)[:2]) and not got & set(list(PR33)[2:])
    # the row's cell is the fourth, and is listed where the three were
    assert [w["name"] for w in bench["workloads"]][:4] == OLD_CELLS + [CELL]
    assert [c["name"] for c in bench["configs"]][3] \
        == "sp-topologyspread-5000"
    for m in bench["per_layer"][:32]:
        if m["workloads"][:3] == OLD_CELLS:
            assert m["workloads"][3] == CELL, m["name"]
        else:
            assert CELL not in m["workloads"], m["name"]


# ------------------------------------------------------- cycles by hand

def _cycle33(t, rounds, bound, spread=None, pods=4):
    """``test_perfbench_spans._cycle`` with an auction of ``rounds`` rounds
    that bound ``bound`` of its ``pods`` pods; ``spread``: the batch's
    valid hard constraint rows, None for a program from before PR 33."""
    c = base._cycle(t, binds=[(t + 0.5, t + 0.6, t + 0.61, "binder-lane")]
                    * bound + [(0.0, 0.0, 0.0, None)] * (pods - bound))
    c["meta"] = {"pods": pods, "auction_rounds": rounds}
    if spread is not None:
        c["meta"].update(spread_constraints=spread,
                         spread_buckets=[1, 1], needs_topo=int(spread > 0))
    return c


def _read(name, cycles, of=CELL, **ctx):
    """The reader ``name`` as the cell ``of`` finds it, on ``cycles``."""
    return spec.cell(of, REPO).readers()[name](
        SimpleNamespace(cycles=cycles, **ctx))


def test_the_round_readers_on_cycles_worked_out_by_hand():
    two = [_cycle33(0.0, rounds=2, bound=4, spread=4),
           _cycle33(1.0, rounds=4, bound=2, spread=2)]
    assert _read("auction_rounds_per_cycle.sat", two) == 3.0
    assert _read("auction_admits_per_round.sat", two) == (4 / 2 + 2 / 4) / 2
    assert _read("spread_constraints_per_cycle.sat", two) == 3.0
    # a cycle that ran no round (nothing popped survived to the auction)
    # is left out of all three, not counted as 0
    idle = _cycle33(2.0, rounds=0, bound=0, spread=0)
    for name, want in (("auction_rounds_per_cycle.sat", 3.0),
                       ("auction_admits_per_round.sat", 1.25),
                       ("spread_constraints_per_cycle.sat", 3.0)):
        assert _read(name, two + [idle]) == want
        assert _read(name, [idle]) is None and _read(name, []) is None
    # the first two read any cell
    assert _read("auction_rounds_per_cycle.sat", two,
                 of=OLD_CELLS[0]) == 3.0
    # a plain batch says 0 constraints: a reading, not a gap
    plain = [_cycle33(0.0, rounds=1, bound=4, spread=0)]
    assert _read("spread_constraints_per_cycle.sat", plain) == 0.0


def test_a_program_that_does_not_say_reads_none_and_nothing_raises():
    parent = [_cycle33(0.0, rounds=2, bound=4), _cycle33(1.0, 4, 2)]
    assert _read("spread_constraints_per_cycle.sat", parent) is None
    # one cycle of a run that does not say: nothing is averaged
    assert _read("spread_constraints_per_cycle.sat",
                 parent[:1] + [_cycle33(2.0, 2, 4, spread=4)]) is None
    # the round count is on every record since PR 25
    assert _read("auction_rounds_per_cycle.sat", parent) == 3.0
    assert _read("auction_admits_per_round.sat", parent) == 1.25
    # a record from before the bind table (PR 26) has no rows to count
    for c in parent:
        del c["binds"]
    assert _read("auction_admits_per_round.sat", parent) is None


# ------------------------------------------------- the count, by hand

def test_spread_ops_on_a_hand_worked_shape():
    """Three zones, 4 pods of one constraint each, 2 rounds, 6 nodes, 10
    countable pods, one-label selectors."""
    # pods proposing over the rounds, at the least: all 4 in round one,
    # one left for round two = 4 + 2 * 1 / 2
    assert spread.pod_rounds(4, 2) == 5.0
    assert spread.pod_rounds(1024, 1) == 1024.0
    assert spread.pod_rounds(1024, 342) == 1024 + 342 * 341 / 2
    once = 4 * 1 * 10 * 3            # (constraint, pod) pairs x 3
    per_round = 5.0 * (5 * 6 + 2)    # skew test a node + the minimum
    adds = 4
    assert spread.ops(4, 6, 2, 10, 1, 1.0, 3) == once + per_round + adds
    # two labels a selector: five operations a pair
    assert spread.ops(4, 6, 2, 10, 1, 2.0, 3) == 4 * 10 * 5 + per_round + 4
    # no constraint, nothing added
    assert spread.ops(4, 6, 2, 10, 0) == 0.0
    assert spread.bytes_moved(4, 6, 2, 10, 1, pairs=3) \
        == 4.0 * (3 * 10 + 5 * 4 + 6 + 2 * 3 * 2)


def test_the_rows_shapes_come_from_its_file_alone():
    row = spec.cell(CELL, REPO).config
    assert spread.shapes_of(row, world) == {
        "constraints_per_pod": 1.0, "labels_per_selector": 1.0,
        "keys": 1.0, "pairs": 3.0}
    basic = spec.cell(OLD_CELLS[0], REPO).config
    assert spread.shapes_of(basic, world)["constraints_per_pod"] == 0.0
    pk = peaks.peak("TPU v5 lite")
    least = spread.least_seconds(1024, 5000, 342, pk.flops_per_s,
                                 pk.bytes_per_s, 6024, 1.0, 1.0, 3.0, 1.0)
    plain = auction.least_seconds(1024, 5000, 342, pk.flops_per_s,
                                  pk.bytes_per_s)
    assert least["spread_ops"] == spread.ops(1024, 5000, 342, 7048, 1, 1.0,
                                             3.0)
    assert least["ops_seconds"] == pytest.approx(
        plain["ops_seconds"] + least["spread_ops"] / pk.flops_per_s)
    assert least["bound"] == "operations"


@pytest.mark.parametrize("rounds,seconds", [(342, 2.9), (1024, 9.0),
                                            (2, 0.02)])
def test_the_roofline_reader_stays_under_100_at_any_padding(rounds, seconds):
    """The count is over valid rows and the least pods a round, the traced
    time over whatever buckets the program ran: the share reads the same
    for a batch padded to 1,024 or 4,096, and far under 100%."""
    cell = spec.cell(CELL, REPO)
    trace = {"modules": {"jit__schedule_gang(1)": {"count": 2,
                                                   "seconds": 2 * seconds}}}
    ctx = dict(cell=cell, trace=trace, device={"kind": "TPU v5 lite"},
               n_nodes=5000, resident_pods=6024)
    cycles = [_cycle33(0.0, rounds=rounds, bound=1024, spread=1024,
                       pods=1024)]
    got = _read("auction_spread_roofline", cycles, **ctx)
    pk = peaks.peak("TPU v5 lite")
    want = (auction.ops(1024, 5000, rounds)
            + spread.ops(1024, 5000, rounds, 7048, 1, 1.0, 3.0)) \
        / pk.flops_per_s
    assert got == pytest.approx(100.0 * want / seconds)
    assert 0 < got < 100
    for c in cycles:                     # the buckets do not enter
        c["meta"]["spread_buckets"] = [4, 64]
    assert _read("auction_spread_roofline", cycles, **ctx) == got
    # nothing to read: no auction in the trace, no round count, or a row
    # whose measured pods carry no hard constraint
    assert _read("auction_spread_roofline", cycles,
                 **dict(ctx, trace={"modules": {}})) is None
    assert _read("auction_spread_roofline", [], **ctx) is None
    assert _read("auction_spread_roofline", cycles, **dict(
        ctx, cell=spec.cell(OLD_CELLS[0], REPO))) is None
