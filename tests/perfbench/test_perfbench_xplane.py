"""The xplane -> metrics reduction against traces with known numbers: a
synthetic one whose answers are worked out by hand, and the small trace
recorded on a TPU v5e that is committed in perfbench/testdata."""

import json
import os

import pytest

from perfbench.lib import xplane

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "perfbench", "testdata")

# times in the text are picoseconds from the line's timestamp_ns.
# device 0, "XLA Ops":   [1.0,3.0) fusion.1   [2.0,4.0) while.2
#                        [6.0,7.0) fusion.1                     (ms)
#   busy = [1,4) + [6,7) = 4 ms; window = first to last event = 1..9 ms
#   (the module line ends at 9); idle = [4,6) and [7,9)
# host: "Scheduling:prepare" [3.5,5.0) ms, "Scheduling:commit" [5.0,8.0) ms
#   idle by phase: prepare 1.0 (4..5), commit 1.0 (5..6) + 1.0 (7..8),
#   nothing open 1.0 (8..9)
SYNTHETIC = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 2000000000 }
    events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 2000000000 }
    events { metadata_id: 1 offset_ps: 6000000000 duration_ps: 1000000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 1000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 6000000000 duration_ps: 1000000000 }
    events { metadata_id: 4 offset_ps: 8500000000 duration_ps: 500000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }
  event_metadata { key: 2 value { id: 2 name: "while.2" } }
  event_metadata { key: 3 value { id: 3 name: "jit__schedule_gang(123)" } }
  event_metadata { key: 4 value { id: 4 name: "jit__apply_cluster_delta(9)" } }
}
planes {
  name: "/host:CPU"
  lines { name: "kubetpu-scheduler" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 3500000000 duration_ps: 1500000000 }
    events { metadata_id: 2 offset_ps: 5000000000 duration_ps: 3000000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "Scheduling:prepare" } }
  event_metadata { key: 2 value { id: 2 name: "Scheduling:commit" } }
  event_metadata { key: 3 value { id: 3 name: "some other TraceMe" } }
}
"""


@pytest.fixture(scope="module")
def synthetic():
    from jax.profiler import ProfileData
    return xplane.summarize(ProfileData.from_text_proto(SYNTHETIC))


def test_busy_is_the_union_of_the_device_operations(synthetic):
    assert synthetic["busy_s"] == pytest.approx(4e-3)
    assert synthetic["window_s"] == pytest.approx(8e-3)
    assert synthetic["devices"][0]["n_ops"] == 3


def test_operations_and_programs_are_summed_by_name(synthetic):
    assert synthetic["ops"][0][0] == "fusion.1"
    assert synthetic["ops"][0][1] == pytest.approx(3e-3)
    assert synthetic["ops"][1] == ["while.2", pytest.approx(2e-3)]
    assert xplane.module_seconds(synthetic, "schedule_gang") == (
        2, pytest.approx(4e-3))
    assert xplane.module_seconds(synthetic, "nothing") == (0, 0.0)


def test_idle_gaps_are_named_by_the_host_phase(synthetic):
    gaps = dict(synthetic["idle_gaps"])
    assert gaps["Scheduling:commit"] == pytest.approx(2e-3)
    assert gaps["Scheduling:prepare"] == pytest.approx(1e-3)
    assert gaps[xplane.IDLE_LABEL] == pytest.approx(1e-3)
    assert sum(gaps.values()) == pytest.approx(
        synthetic["window_s"] - synthetic["busy_s"])


def test_a_trace_without_a_device_plane_is_an_error():
    from jax.profiler import ProfileData
    host_only = SYNTHETIC[SYNTHETIC.index('planes {\n  name: "/host'):]
    with pytest.raises(ValueError, match="no /device:TPU"):
        xplane.summarize(ProfileData.from_text_proto(host_only))


def test_the_recorded_trace_reduces_to_the_committed_numbers():
    path = os.path.join(TESTDATA, "v5e_small.xplane.pb")
    with open(os.path.join(TESTDATA, "v5e_small.expected.json")) as f:
        want = json.load(f)
    got = xplane.summarize(xplane.load(path))
    assert got["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert got["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0 < got["busy_s"] < got["window_s"]
    for name, m in want["modules"].items():
        assert got["modules"][name]["count"] == m["count"]
        assert got["modules"][name]["seconds"] == pytest.approx(
            m["seconds"], rel=1e-9)
    assert [o[0] for o in got["ops"]] == [o[0] for o in want["ops"]]
    assert dict(got["idle_gaps"]).keys() == dict(want["idle_gaps"]).keys()
    # the phases the recording opened by hand are there by name
    assert any(k.startswith("Scheduling:") for k, _ in got["idle_gaps"])
