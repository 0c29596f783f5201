"""chip_smoke.py's host re-check is what decides *correct* on the chip, so
it gets its own test: fed placements that break each rule, it must flag
each one — and stay quiet on a clean placement.  (The script itself only
passes on a TPU; ``python chip_smoke.py`` on the cpu backend must exit
nonzero without scheduling anything.)"""
import os
import subprocess
import sys

import chip_smoke
from kubetpu.harness import hollow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bound(pod, node):
    pod.spec.node_name = node.metadata.name
    return pod


def test_clean_placement_has_no_violations():
    nodes = hollow.make_nodes(2)
    pods = [_bound(hollow.make_pod(f"p{i}", cpu_milli=1000), nodes[i % 2])
            for i in range(6)]
    binds = [(f"default/p{i}", nodes[i % 2].metadata.name) for i in range(6)]
    assert chip_smoke.host_recheck(nodes, pods, binds) == []


def test_overcommitted_node_and_double_bind_are_both_flagged():
    nodes = hollow.make_nodes(2)                     # 4 CPU, 32 Gi each
    pods = [_bound(hollow.make_pod(f"p{i}", cpu_milli=2000), nodes[0])
            for i in range(3)]                       # 6 CPU on node-0
    binds = [("default/p0", "node-0"), ("default/p1", "node-0"),
             ("default/p2", "node-0"), ("default/p0", "node-1")]
    bad = chip_smoke.host_recheck(nodes, pods, binds)
    assert any("node-0 over-committed on cpu" in v for v in bad), bad
    assert any("default/p0 bound 2 times" in v for v in bad), bad
    assert len(bad) == 2, bad


def test_memory_podcount_unknown_node_and_anti_affinity_are_flagged():
    nodes = hollow.make_nodes(2, pods=2)
    big = _bound(hollow.make_pod("big", mem=40 << 30), nodes[0])
    crowd = [_bound(hollow.make_pod(f"c{i}"), nodes[1]) for i in range(3)]
    lost = hollow.make_pod("lost")
    lost.spec.node_name = "node-9"
    bad = chip_smoke.host_recheck(nodes, [big, lost] + crowd, [])
    assert any("node-0 over-committed on memory" in v for v in bad), bad
    assert any("node-1 over-committed on pods" in v for v in bad), bad
    assert any("unknown node node-9" in v for v in bad), bad

    nodes = hollow.make_nodes(1)
    a = _bound(hollow.make_pod("a", labels={"app": "x"}), nodes[0])
    b = _bound(hollow.make_pod("b", labels={"app": "x"}), nodes[0])
    hollow.with_anti_affinity(a)
    bad = chip_smoke.host_recheck(nodes, [a, b], [])
    assert bad == ["anti-affinity pair on node-0: a / b"]


def test_predicted_unbound_counts_anti_affinity_groups():
    nodes = hollow.make_nodes(3)
    held = _bound(hollow.make_pod("held", labels={"app": "x"}), nodes[0])
    pending = [hollow.with_anti_affinity(
        hollow.make_pod(f"x{i}", labels={"app": "x"})) for i in range(4)]
    pending += [hollow.make_pod(f"free{i}") for i in range(5)]
    # group x: 4 members, 3 nodes, one already holds an app=x pod -> 2 fit
    assert chip_smoke.predicted_unbound(nodes, [held], pending) == 2


def test_parse_quantity():
    q = chip_smoke.parse_quantity
    assert q("100m") == 0.1 and q("4") == 4.0
    assert q("32Gi") == 32 * 2 ** 30 and q(str(256 << 20)) == 256 << 20


def test_refuses_to_run_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "platform: cpu" in proc.stdout
    assert '"ok"' not in proc.stdout and "phase " not in proc.stdout
