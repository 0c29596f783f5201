"""The eight readers PR 38 added (perfbench/lib/threads.py and their
metric files) on a cycle record worked out by hand, on the recorded v5e
capture's cycles (a program from before PR 38, which says none of it),
their entries in BENCHMARK.json, the health pass of
perfbench/tools/interp_report.py, and the lane's ``Binding:bind-job``
host event, which must not enter ``idle_gaps``.  A file of its own,
beside test_perfbench_spans.py whose helpers it borrows: a PR that adds a
metric adds files to the benchmark and edits none."""

import copy
import json
import os
from types import SimpleNamespace

import pytest

import perfbench_toy
import test_perfbench_spans as base
import test_perfbench_xplane as xbase
from perfbench.lib import drive, spec, threads, xplane
from perfbench.tools import interp_report, later_pr_tree

REPO = perfbench_toy.REPO
CELLS = ["sp-basic-5000.saturated", "sp-antiaffinity-5000.saturated",
         "sp-mixed-5000.saturated", "sp-topologyspread-5000.saturated",
         "sigscale-150k.saturated"]
LANE = "binder-lane"
# name -> (unit, source, layer), in the order they were appended
ENTRIES = {
    "lane_cpu_ms_per_cycle.sat": ("ms", "program_span", "commit and bind"),
    "lane_blocked_pct.sat": ("%", "program_span", "commit and bind"),
    "handover_wait_ms_per_cycle.sat": ("ms", "program_span",
                                       "commit and bind"),
    "python_cpu_ms_per_cycle.sat": ("ms", "program_counter", "interpreter"),
    "other_threads_cpu_ms_per_cycle.sat": ("ms", "program_counter",
                                           "interpreter"),
    "gc_pause_ms_per_cycle.sat": ("ms", "program_span", "interpreter"),
    "gc_full_collections_per_cycle.sat": ("count", "program_span",
                                          "interpreter"),
    "tensorize_row_maps_ms_per_cycle.sat": ("ms", "program_span",
                                            "prepare"),
}
FIRST = 44          # the per-layer metrics that were there before


def _cycle38(t):
    """``test_perfbench_spans._cycle`` as a program since PR 38 records
    it: a root on the serving thread, the hand-over's wait on ``commit``
    (80 of its 200 ms ``submit_s``), ``row-maps`` 12 ms inside
    ``tensorize``, a full collection of 30 ms under ``snapshot`` and a
    young one of 2 ms under ``commit``, and the lane's job: 400 ms from
    t + 0.96, of which the lane ran 100 and the collector 8, over four
    rows of the bind table."""
    c = base._cycle(t, binds=[
        (t + 0.5, t + 0.97, t + 1.0, LANE), (t + 0.5, t + 1.0, t + 1.1, LANE),
        (t + 0.5, t + 1.1, t + 1.2, LANE), (t + 0.5, t + 1.2, t + 1.35, LANE),
        (0.0, 0.0, 0.0, None)])
    for i, s in enumerate(c["spans"]):
        s["id"] = i + 2
    c["spans"].insert(0, dict(base._span("Scheduling", t, t + 0.95),
                              id=1, parent=0))
    by = {s["name"]: s for s in c["spans"]}
    by["snapshot"]["args"].update(gc_s=0.03, gc_full=1)
    by["commit"]["args"].update(handover_wait_s=0.08, gc_s=0.002,
                                bind_jobs=1, binds_pooled=0)
    c["spans"].append(dict(base._span("row-maps", t + 0.25, t + 0.262,
                                      pod_rows=5000), id=20))
    c["spans"].append(dict(
        base._span("bind-job", t + 0.96, t + 1.36, pods=4, cpu_s=0.1,
                   settle_s=0.01, wake_s=0.01, pooled=0, gc_s=0.008),
        id=21, parent=by["commit"]["id"], thread=LANE))
    c["events"].append({"name": "gc", "ts": t + 0.05,
                        "parent": by["snapshot"]["id"], "thread": "serving",
                        "args": {"generation": 2, "seconds": 0.03,
                                 "collected": 7}})
    # the phases' cpu_s add up to 0.731: the serving thread's entry
    c["meta"] = {"thread_cpu_s": {"serving": 0.731, LANE: 0.1,
                                  "perfbench-client": 0.15,
                                  "binder_pool": 0.004, "Thread-7": 0.016},
                 "thread_cpu_window_s": 1.0, "gc_other_s": 0.01,
                 "gc_collections": 9}
    return c


WANT = {
    "lane_cpu_ms_per_cycle.sat": 100.0,
    "lane_blocked_pct.sat": 75.0,                  # 1 - 0.1 / 0.4
    "handover_wait_ms_per_cycle.sat": 80.0,
    "python_cpu_ms_per_cycle.sat": 1001.0,
    "other_threads_cpu_ms_per_cycle.sat": 170.0,   # client, pool, Thread-7
    "gc_pause_ms_per_cycle.sat": 50.0,             # 30 + 2 + 8 + 10 other
    "gc_full_collections_per_cycle.sat": 1.0,
    "tensorize_row_maps_ms_per_cycle.sat": 12.0,
}


def _read(name, cycles, of=CELLS[0], root=REPO):
    return spec.cell(of, root).readers()[name](base._ctx(cycles, root=root))


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_reader_on_a_cycle_worked_out_by_hand(name):
    one = [_cycle38(0.0)]
    assert _read(name, one) == pytest.approx(WANT[name], rel=1e-9)
    # a second cycle whose collector was quiet and whose lane was quick:
    # the means halve what is a sum a cycle, and nothing is skipped
    quiet = _cycle38(2.0)
    for s in quiet["spans"]:
        s["args"].pop("gc_s", None), s["args"].pop("gc_full", None)
    del quiet["meta"]["gc_other_s"]
    got = _read(name, one + [quiet])
    if name.startswith("gc_"):
        assert got == pytest.approx(WANT[name] / 2, rel=1e-9)
    else:
        assert got == pytest.approx(WANT[name], rel=1e-9)
    for cell in CELLS:                              # every cell reads it
        assert _read(name, one, of=cell) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", list(ENTRIES))
def test_a_program_that_does_not_say_reads_none_and_nothing_raises(name):
    assert _read(name, []) is None
    assert _read(name, base.TWO) is None            # PR 26's hand cycles
    with open(os.path.join(base.TESTDATA, "v5e_clock.cycles.json")) as f:
        recorded = json.load(f)
    assert recorded and not any(
        s["name"] in ("bind-job", "row-maps") or "gc_s" in s["args"]
        or "handover_wait_s" in s["args"]
        for c in recorded for s in c["spans"])
    assert not any("thread_cpu_s" in c["meta"] for c in recorded)
    assert _read(name, recorded) is None


def test_a_platform_without_thread_clocks_reads_none_for_the_two_alone():
    c = _cycle38(0.0)
    del c["meta"]["thread_cpu_s"], c["meta"]["thread_cpu_window_s"]
    for name in ENTRIES:
        got = _read(name, [c])
        if "cpu_ms" in name and not name.startswith("lane_"):
            assert got is None
        else:
            assert got == pytest.approx(WANT[name])
    # the collector hooked and quiet all window long is a reading of 0
    for s in c["spans"]:
        s["args"].pop("gc_s", None), s["args"].pop("gc_full", None)
    del c["meta"]["gc_other_s"]
    assert _read("gc_pause_ms_per_cycle.sat", [c]) == 0.0
    assert _read("gc_full_collections_per_cycle.sat", [c]) == 0.0


def test_the_threads_by_name_for_the_report():
    by = threads.thread_cpu_ms_by_name([_cycle38(0.0), _cycle38(2.0)])
    assert by["perfbench-client"] == pytest.approx(150.0)
    assert by["(window)"] == pytest.approx(1000.0)
    assert threads.serving_thread(_cycle38(0.0)) == "serving"
    assert threads.thread_cpu_ms_by_name(base.TWO) == {}


def test_the_report_counts_the_cycles_that_break_a_promise():
    good = [_cycle38(0.0), _cycle38(2.0)]
    rep = interp_report.structure(good)
    assert not any(rep["violations"].values()), rep["violations"]
    assert rep["bind_job_ms"] == pytest.approx(400.0)
    assert rep["bind_job_less_lane_busy_ms_mean_max"] == [
        pytest.approx(20.0), pytest.approx(20.0)]     # 0.97..1.35 inside
    assert rep["tensorize_under_no_child_ms"] == pytest.approx(38.0)
    assert rep["gc_full_by_span"] == {"snapshot": 2}
    assert rep["phase_cpu_over_thread_cpu_min_median_max"] == [1.0] * 3
    assert len(rep["gc_events"]) == 2 and rep["max_spans"] == 10
    bad = copy.deepcopy(good[0])
    by = {s["name"]: s for s in bad["spans"]}
    by["bind-job"]["args"]["cpu_s"] = 0.5              # over its extent
    by["bind-job"]["t0"] += 0.05                       # misses a lane row
    by["commit"]["args"]["handover_wait_s"] = 0.3      # over submit_s
    by["snapshot"]["args"]["gc_s"] = 0.2               # over the phase
    bad["meta"]["thread_cpu_s"]["serving"] = 0.9       # 19% off the phases
    assert interp_report.structure([bad])["violations"] == {
        "jobs_a_cycle": 0, "job_misses_lane_rows": 1,
        "job_cpu_over_extent": 1, "handover_over_submit": 1,
        "gc_over_extent": 1, "phase_cpu_off_thread_cpu": 1}
    # a program from before PR 38 says nothing and breaks nothing
    old = interp_report.structure(base.TWO)
    assert not any(old["violations"].values())
    assert old["bind_job_ms"] is None and old["python_cpu_ms"] is None
    assert old["gc_pause_ms"] is None and old["thread_cpu_ms_by_name"] == {}


@pytest.fixture(scope="module")
def toy_traced(tmp_path_factory):
    """The toy anti-affinity cell listed for every metric of the real
    cells, run traced through the whole of drive.run_cell on the CPU."""
    from kubetpu.utils import sanitize
    root = perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy38")))
    base._list_the_toy_cell_for_every_metric(root)   # what they split
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
    armed = list(sanitize._watchdogs)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(drive, "SimpleNamespace", keep)
        try:
            res = drive.run_cell(cell, seed=2 ** 31 + 38, seconds=3.0,
                                 trace=True, require_tpu=False,
                                 out=said.append)
        finally:
            # a run never takes its compile watchdog off; a test process
            # lives on
            for wd in list(sanitize._watchdogs):
                if wd not in armed:
                    sanitize.uninstall_compile_watchdog(wd)
    return res, kept, "\n".join(said)


def test_a_traced_toy_run_fills_the_eight_and_keeps_every_promise(
        toy_traced):
    res, ctx, said = toy_traced
    assert res["correct"] is True, said
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) >= set(ENTRIES)
    assert 0 < got["lane_cpu_ms_per_cycle.sat"] \
        <= got["lane_busy_ms_per_cycle.sat"] + 1.0
    # a toy job lasts a millisecond or two, and its two clocks are read
    # microseconds apart: the CPU clock can come out a hair over the wall
    assert -5 < got["lane_blocked_pct.sat"] < 100
    assert 0 <= got["handover_wait_ms_per_cycle.sat"] \
        <= got["commit_submit_ms_per_cycle.sat"] + 1e-3
    assert 0 <= got["other_threads_cpu_ms_per_cycle.sat"] \
        < got["python_cpu_ms_per_cycle.sat"]
    assert got["gc_pause_ms_per_cycle.sat"] >= 0
    assert got["gc_full_collections_per_cycle.sat"] >= 0
    assert 0 < got["tensorize_row_maps_ms_per_cycle.sat"] \
        < got["tensorize_ms_per_cycle.sat"]
    # the client's thread is there by name, as the cells' runs report it
    rep = interp_report.structure(ctx["cycles"])
    assert rep["thread_cpu_ms_by_name"]["perfbench-client"] > 0
    assert rep["thread_cpu_ms_by_name"]["binder-lane"] > 0
    bad = dict(rep["violations"])
    # a toy cycle lasts a few ms: what lies between two phases is over a
    # twentieth of some (tests/test_thread_cpu_meta.py holds a quiet
    # cycle to it, a run on the chip the real size)
    bad.pop("phase_cpu_off_thread_cpu")
    assert not any(bad.values()), rep["violations"]
    assert rep["span_drops"] == rep["event_drops"] == 0
    assert rep["max_spans"] <= 32
    assert abs(rep["bind_job_less_lane_busy_ms_mean_max"][0]) < 5.0


@pytest.fixture(scope="module")
def later_root(tmp_path_factory):
    return later_pr_tree.build(
        os.path.join(str(tmp_path_factory.mktemp("later38")), "checkout"))


@pytest.mark.parametrize("later", [False, True],
                         ids=["as-committed", "with-entries-added"])
def test_benchmark_json_names_the_eight_after_the_44_that_were_there(
        later, later_root):
    """Held by name and by the place PR 38 appended at, never as the
    list's tail: a later PR appends entries of its own."""
    root = later_root if later else REPO
    bench = spec.load_benchmark(root)
    got = bench["per_layer"][FIRST:FIRST + len(ENTRIES)]
    assert [m["name"] for m in got] == list(ENTRIES)
    for m in got:
        unit, source, layer = ENTRIES[m["name"]]
        assert {k: v for k, v in m.items() if k != "workloads"} == {
            "name": m["name"], "unit": unit, "better": "lower",
            "source": source, "layer": layer, "moves": "pods_bound_per_s"}
        assert m["workloads"][:5] == CELLS
        for cell in CELLS:
            assert m["name"] in spec.cell(cell, root).readers()
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.count(n) == 1 for n in ENTRIES)


# ------------------------------------------------ the lane's annotation

# test_perfbench_xplane's synthetic capture with the lane's job on a host
# line of its own, [3.0, 9.0) ms: over every idle gap of the device
WITH_JOB = xbase.SYNTHETIC.replace('''  event_metadata { key: 1 value { id: 1 name: "Scheduling:prepare" } }''', '''  lines { name: "binder-lane" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 3000000000 duration_ps: 6000000000 }
  }
  event_metadata { key: 4 value { id: 4 name: "Binding:bind-job" } }
  event_metadata { key: 1 value { id: 1 name: "Scheduling:prepare" } }''')


def test_the_lanes_annotation_stays_out_of_the_idle_partition():
    from jax.profiler import ProfileData
    assert WITH_JOB != xbase.SYNTHETIC
    plain = xplane.summarize(ProfileData.from_text_proto(xbase.SYNTHETIC))
    with_job = xplane.summarize(ProfileData.from_text_proto(WITH_JOB))
    assert with_job["idle_gaps"] == plain["idle_gaps"]
    assert {name for name, _ in with_job["idle_gaps"]} == {
        "Scheduling:commit", "Scheduling:prepare", xplane.IDLE_LABEL}
    assert with_job["busy_s"] == plain["busy_s"]
    # the event is in the capture, under its own name, for Perfetto
    tree = xplane.planes(ProfileData.from_text_proto(WITH_JOB))
    assert [e[0] for e in tree["/host:CPU"]["binder-lane"]] == [
        "Binding:bind-job"]
    assert len(xplane.host_phases(tree, "Binding:")) == 1
