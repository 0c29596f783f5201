"""The two readers PR 48 added (perfbench/metrics/batch_rows_shared_pct.sat,
classify_ms_per_cycle.sat): on cycle records worked out by hand, on records
of a program that builds a batch row a pod and does not group (the parent:
None, never 0, never raises), in a traced toy run of the whole harness, and
their entries in BENCHMARK.json.  A file of its own, beside
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
SHARED = "batch_rows_shared_pct.sat"
CLASSIFY = "classify_ms_per_cycle.sat"
ENTRIES = {SHARED: ("%", "higher"), CLASSIFY: ("ms", "lower")}
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated", "sp-prefaffinity-5000.saturated",
         "sp-prefspread-5000.saturated", "sp-podaffinity-5000.saturated"]


def _reader(name, cell=CELLS[-1], root=REPO):
    return spec.cell(cell, root).readers()[name]


def _cycle48(t, pods=1024, classes=None, built=None, classify_ms=()):
    """PR 28's hand cycle with a batch-build span of 5 ms over ``pods``
    pods; ``classes`` / ``built``: what a program since PR 48 says of it,
    None for one that builds a row a pod; ``classify_ms``: its classify
    spans (the first inside prefilter, the rest inside tensorize)."""
    c = base._cycle28(t)
    args = {"pods": pods, "spread_rows": 0, "ra_rows": 0,
            "term_sets_live": []}
    if built is not None:
        args.update(pod_classes=classes, rows_built=built)
    c["spans"].append(base._span("batch-build", t + 0.2, t + 0.205, **args))
    at = [t + 0.05] + [t + 0.08 + 0.01 * i for i in range(len(classify_ms))]
    for t0, ms in zip(at, classify_ms):
        c["spans"].append(base._span("classify", t0, t0 + ms / 1e3,
                                     pods=pods))
    return c


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_benchmark_json_names_it_for_all_eight_cells(name):
    bench = spec.load_benchmark(REPO)
    m, = [m for m in bench["per_layer"] if m["name"] == name]
    unit, better = ENTRIES[name]
    assert {k: v for k, v in m.items() if k != "workloads"} == {
        "name": name, "unit": unit, "better": better,
        "source": "program_span", "layer": "prepare",
        "moves": "pods_bound_per_s"}
    # a later PR's cell may list itself
    assert m["workloads"][:8] == CELLS
    for cell in CELLS:
        assert name in spec.cell(cell, REPO).readers()


def test_the_shared_rows_on_cycles_worked_out_by_hand():
    read = _reader(SHARED)
    # one class: 1,023 of 1,024 rows gathered; ten: 1,014
    assert read(base._ctx([_cycle48(0.0, 1024, 1, 1)])) == pytest.approx(
        100.0 * 1023 / 1024)
    two = [_cycle48(0.0, 1024, 10, 10), _cycle48(1.0, 512, 2, 2)]
    assert read(base._ctx(two)) == pytest.approx(
        (100.0 * 1014 / 1024 + 100.0 * 510 / 512) / 2)
    # every pod its own class: a row a pod, nothing shared, which is a
    # reading
    assert read(base._ctx([_cycle48(0.0, 1024, 1024, 1024)])) == 0.0
    # a cycle that built no batch (it failed in PreFilter) has no say
    bare = base._cycle28(2.0)
    assert read(base._ctx(two[:1] + [bare])) == pytest.approx(
        100.0 * 1014 / 1024)
    for cell in CELLS:                              # every cell reads it
        assert _reader(SHARED, cell)(base._ctx(two[:1])) == pytest.approx(
            100.0 * 1014 / 1024)


def test_the_classify_span_on_cycles_worked_out_by_hand():
    read = _reader(CLASSIFY)
    # the grouping inside prefilter and the shared PodInfos inside
    # tensorize are one reading: 2 + 3 and 1 + 2 ms
    two = [_cycle48(0.0, 1024, 1, 1, classify_ms=(2.0, 3.0)),
           _cycle48(1.0, 1024, 1, 1, classify_ms=(1.0, 2.0))]
    assert read(base._ctx(two)) == pytest.approx(4.0)
    # a cycle without the span is left out of the mean
    assert read(base._ctx(two + [base._cycle28(2.0)])) == pytest.approx(4.0)


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_a_program_that_does_not_say_reads_none_and_nothing_raises(name):
    """The parent of PR 48 builds a row a pod: its batch-build span holds
    ``pods`` and no ``rows_built``, and it has no classify span."""
    read = _reader(name)
    parent = [_cycle48(0.0), _cycle48(1.0)]
    assert read(base._ctx(parent)) is None
    assert read(base._ctx([])) is None
    assert read(base._ctx(base.TWO)) is None        # PR 26's hand cycles
    assert read(base._ctx(base.TWO28)) is None      # PR 28's
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and read(base._ctx(recorded)) is None
    old = {"seq": 1, "t0": 0.0, "t1": 1.0, "meta": {}, "events": [],
           "spans": [base._span("dispatch", 0.3, 0.4)]}
    assert read(base._ctx([old])) is None
    if name == SHARED:
        # one cycle of the window that does not say: no reading
        assert read(base._ctx(parent[:1] + [_cycle48(1.0, 8, 1, 1)])) is None


def test_a_traced_toy_run_shares_its_rows(tmp_path, monkeypatch):
    """The toy anti-affinity cell (every pod of a cycle from one
    template: one class), listed for both metrics, through the whole of
    drive.run_cell on the CPU."""
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
        res = drive.run_cell(cell, seed=2 ** 31 + 48, seconds=3.0,
                             trace=True, require_tpu=False,
                             out=said.append)
    finally:
        for wd in list(sanitize._watchdogs):
            if wd not in armed:
                sanitize.uninstall_compile_watchdog(wd)
    assert res["correct"] is True, "\n".join(said)
    shared, classify = res["metrics"][SHARED], res["metrics"][CLASSIFY]
    assert shared["unit"] == "%" and shared["value"] > 90.0
    assert classify["unit"] == "ms" and classify["value"] > 0.0
    builds = [(c, s) for c in kept["cycles"] for s in c["spans"]
              if s["name"] == "batch-build"]
    assert builds
    for c, s in builds:
        a = s["args"]
        assert 1 <= a["pod_classes"] <= a["rows_built"] <= a["pods"]
        # one template, one class, one row built whatever the batch holds
        assert a["pod_classes"] == a["rows_built"] == 1
        assert c["meta"]["pod_classes"] == 1 and c["meta"]["rows_built"] == 1
        # the grouping inside prefilter, the shared PodInfos in tensorize
        phases = {p["name"]: p for p in c["spans"]
                  if p["name"] in ("prefilter", "tensorize")}
        spans = [p for p in c["spans"] if p["name"] == "classify"]
        assert len(spans) == 2
        for p, phase in zip(spans, ("prefilter", "tensorize")):
            assert (phases[phase]["t0"] <= p["t0"] <= p["t1"]
                    <= phases[phase]["t1"])
