"""run.py and one whole run of a cell: it refuses without a TPU and in a
bare directory; a toy cell added purely as data runs through the whole
of a run on the CPU with ``correct`` true; with the timed path broken
underneath, ``correct`` comes out false; and each configuration's
control, patched into the program, fails check (b)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import perfbench_toy
from perfbench.lib import drive, spec

REPO = perfbench_toy.REPO
RUN = [sys.executable, os.path.join("perfbench", "run.py"), "--seed", "1",
       "--seconds", "1", "--trace", "0", "--workload"]


def _run(cwd, workload="sp-basic-5000.saturated"):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="driver-noise")
    return subprocess.run(RUN + [workload], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_run_py_refuses_to_measure_without_a_tpu():
    p = _run(REPO)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")      # no result line


def test_run_py_refuses_in_a_bare_directory(tmp_path):
    shutil.copytree(os.path.join(REPO, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".scratch"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not p.stdout.strip().endswith("}")


def test_run_py_refuses_an_unknown_cell():
    p = _run(REPO, "no-such.cell")
    assert p.returncode != 0 and "no workload" in p.stderr


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return perfbench_toy.make_root(str(tmp_path_factory.mktemp("toy")))


def _read_in_every_cell(root):
    """The per-layer metrics that list no ``workloads``: read in every
    cell that reports what they move, the toy cells too.  There is none
    today; a later PR may add one."""
    return {m["name"] for m in spec.load_benchmark(root)["per_layer"]
            if "workloads" not in m}


def test_a_cell_added_as_data_resolves_and_nothing_else_changed(toy_root):
    cell = spec.cell("toy-anti-96.closed", toy_root)
    assert set(cell.readers()) == {"toy_cycles"} | _read_in_every_cell(
        toy_root)
    assert [m["name"] for m in cell.end_to_end] == ["pods_bound_per_s",
                                                    "setup_s"]
    # the benchmark's own files went in unchanged
    for sub in ("lib/drive.py", "lib/client.py", "lib/world.py",
                "lib/check.py", "lib/spec.py", "run.py",
                "configs/sp-basic-5000.json",
                "configs/sp-antiaffinity-5000.json",
                "controls/bf16-scores.py", "controls/blind-batch.py",
                "reference/default_plugins.py",
                "traffic/saturated-d4096.json"):
        with open(os.path.join(REPO, "perfbench", sub)) as a, \
                open(os.path.join(toy_root, "perfbench", sub)) as b:
            assert a.read() == b.read()
    # and the cells that were there still resolve
    assert spec.cell("sp-basic-5000.saturated", toy_root).chips == 1


def _lines(collected):
    return "\n".join(collected)


def test_the_toy_cell_runs_traced_and_is_correct(toy_root):
    """The whole of a run but the look for a chip: world, serving path,
    client, warm-up, window, per-layer readers, both checks."""
    cell = spec.cell("toy-anti-96.closed", toy_root)
    said = []
    res = drive.run_cell(cell, seed=2 ** 31 + 11, seconds=2.0, trace=True,
                         require_tpu=False, out=said.append)
    assert res["correct"] is True, _lines(said)
    assert res["failed"] == 0 and res["attempted"] > 0
    assert {"toy_cycles"} <= set(res["metrics"]) \
        <= {"toy_cycles"} | _read_in_every_cell(toy_root)
    assert res["metrics"]["toy_cycles"]["value"] >= 1
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes", "busy_s", "window_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    text = _lines(said)
    assert "correct (a) guarantee violations" in text and "limit 0" in text
    assert "correct (b) placements outside every round" in text
    json.dumps(res)


def test_a_broken_timed_path_comes_out_not_correct(toy_root, monkeypatch):
    """An answer altered where it is produced: every third bind goes to
    node-0 whatever the scheduler chose.  The guarantees break (anti-
    affinity, then the node's room) and ``correct`` must be false."""
    from kubetpu.client.store import ClusterStore
    real_bind = ClusterStore.bind
    count = {"n": 0}

    def bind(self, pod, node_name):
        count["n"] += 1
        if pod.metadata.name.startswith("measured-") \
                and count["n"] % 3 == 0:
            node_name = "node-0"
        return real_bind(self, pod, node_name)
    monkeypatch.setattr(ClusterStore, "bind", bind)
    cell = spec.cell("toy-anti-96.closed", toy_root)
    said = []
    res = drive.run_cell(cell, seed=5, seconds=1.0, trace=False,
                         require_tpu=False, out=said.append)
    assert res["correct"] is False, _lines(said)
    assert "violation:" in _lines(said)
    assert set(res["metrics"]) == {"pods_bound_per_s", "setup_s"}


@pytest.mark.parametrize("name", ["toy-basic-96.closed",
                                  "toy-anti-96.closed"])
def test_the_control_patched_into_the_program_fails_check_b(toy_root, name):
    """Check (b) drives the program's own gang auction at the cell's
    batch size.  As it stands it has no miss; with the configuration's
    control patched in (summed scores in bfloat16; the batch's own pods
    left out of the term filter) it has."""
    from perfbench.lib import check, world
    cell = spec.cell(name, toy_root)
    control = cell.control()
    nodes = world.node_records(cell.config)
    sound, broken = [], []
    for seed in (1, 2, 2 ** 31 + 3):
        init = world.init_records(cell.config, seed)
        sound.append(len(check.gang_check(cell, seed, nodes, init)))
        with control.program_control():
            broken.append(len(check.gang_check(cell, seed, nodes, init)))
    assert sound == [0, 0, 0]
    assert min(broken) >= 1, broken
