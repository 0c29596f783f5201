"""PodTopologySpread's raw score, ``int64(sum of float64(count) *
log(size + 2))``, in ``kubetpu/ops/kernels.py`` ``spread_soft_score``
(PR 42).

Upstream multiplies in float64 and truncates.  Until PR 42 the kernel
floored a float32 product made with the device's own ``log``: for the
three zones of ``sp-prefspread-5000`` that lands on the other side of an
integer first at 4,217 matching pods a zone (host arithmetic; a zone of
this row holds up to ~3,000, a larger cluster's more), and at some count
for every size.  ``log_weighted_floor`` makes the product in integers
from the float64 logarithm itself; this file enumerates it against
float64 for EVERY count in 0..16,384 and EVERY size in 0..8,192 (three
zones; hostname sizes for the general kernel), which is the proof the
configuration's ``precision`` cites, and holds the kernel's score to
hand-worked cases and to the plain reference."""

import fractions
import math

import numpy as np
import pytest

from kubetpu.api import types as api
from tests.harness import run_cluster
from tests.test_tensors import mknode, mkpod

MAX_CNT, MAX_SIZE = 16384, 8192
CHUNK = 512
SOFT = [("PodTopologySpread", 1)]


def _float64(cnt, sizes):
    w = np.array([math.log(s + 2.0) for s in sizes])
    return np.floor(cnt[None, :].astype(np.float64) * w[:, None])


@pytest.fixture(scope="module")
def exact():
    import jax
    import jax.numpy as jnp
    from kubetpu.ops import kernels as K

    @jax.jit
    def run(cnt, sizes):
        c = jnp.broadcast_to(cnt[None, None, :],
                             (sizes.shape[0], 1, cnt.shape[0]))
        return K.log_weighted_floor(c, sizes[:, None],
                                    jnp.ones(c.shape, bool), MAX_SIZE + 2)
    return lambda cnt, sizes: np.asarray(run(jnp.asarray(cnt, jnp.float32),
                                             jnp.asarray(sizes, jnp.float32)))


@pytest.mark.parametrize("lo", range(0, MAX_SIZE + 1, 8 * CHUNK))
def test_the_integer_product_is_the_float64_floor_at_every_pair(exact, lo):
    cnt = np.arange(0, MAX_CNT + 1)
    pairs = 0
    for at in range(lo, min(lo + 8 * CHUNK, MAX_SIZE + 1), CHUNK):
        sizes = np.arange(at, min(at + CHUNK, MAX_SIZE + 1))
        got = exact(cnt, sizes)
        assert (got == _float64(cnt, sizes)).all(), at
        pairs += got.size
    assert pairs == (min(lo + 8 * CHUNK, MAX_SIZE + 1) - lo) * (MAX_CNT + 1)


def test_a_float32_product_is_not(exact):
    """Why the product is not left in float32: with the host's own
    float32 logarithm it floors to another integer at fifteen counts
    for three zones, the first 4,217, and somewhere for every size."""
    cnt = np.arange(0, MAX_CNT + 1)
    want = _float64(cnt, [3])[0]
    f32 = np.floor(cnt.astype(np.float32) * np.log(np.float32(5.0)))
    differ = np.flatnonzero(f32 != want)
    assert differ[0] == 4217 and len(differ) == 15
    assert (exact(cnt, [3])[0] == want).all()
    for size in (0, 1, 2, 100, 1666, 5000, MAX_SIZE):
        w32 = np.log(np.float32(size + 2.0))
        assert (np.floor(cnt.astype(np.float32) * w32)
                != _float64(cnt, [size])[0]).any(), size


def test_the_table_holds_the_hosts_float64_logarithm_exactly():
    from kubetpu.ops import kernels as K
    table = K._log_weight_limbs(4096)
    assert table.shape == (4096, K._LOG_LIMBS) and table.dtype == np.int32
    assert 0 <= table.min() and table.max() < 1 << K._LIMB_BITS
    for k in (0, 1, 3, 1666, 4095):
        fixed = sum(int(table[k, i]) << (K._LIMB_BITS * i)
                    for i in range(K._LOG_LIMBS))
        assert fractions.Fraction(fixed, 1 << K._LOG_FRAC_BITS) \
            == fractions.Fraction(math.log(k + 2.0))
    # three zones: the weight the reference and upstream hold
    assert math.log(5.0) == 1.6094379124341003


def test_constraints_are_summed_before_the_floor():
    """Two constraints a pod: the floor of the SUM of the exact products,
    as upstream truncates the float64 sum, not the sum of two floors;
    entries that are not counted add nothing."""
    import jax.numpy as jnp
    from kubetpu.ops import kernels as K
    rng = np.random.default_rng(42)
    n = 4096
    cnt = rng.integers(0, 1 << 20, size=(2, n))
    sizes = np.array([3, 4999])
    counted = rng.random((2, n)) < 0.8
    got = np.asarray(K.log_weighted_floor(
        jnp.asarray(cnt, jnp.float32), jnp.asarray(sizes, jnp.float32),
        jnp.asarray(counted), 5001))
    w = [fractions.Fraction(math.log(s + 2.0)) for s in sizes]
    want = [math.floor(sum(int(cnt[c, i]) * w[c] for c in range(2)
                           if counted[c, i])) for i in range(n)]
    assert got.tolist() == want
    two_floors = [sum(math.floor(int(cnt[c, i]) * w[c]) for c in range(2)
                      if counted[c, i]) for i in range(n)]
    assert got.tolist() != two_floors
    # and the float64 sum upstream makes agrees on every one of them
    f64 = [int(sum(float(cnt[c, i]) * math.log(sizes[c] + 2.0)
                   for c in range(2) if counted[c, i])) for i in range(n)]
    assert f64 == want


def test_counts_up_to_the_pod_axis_bound_and_the_constraint_cap():
    import jax.numpy as jnp
    from kubetpu.ops import kernels as K
    # the largest counts whose product still reads exactly as a float32
    cnt = np.array([[1_900_000, 262144, 150000, 0]], np.float32)
    got = np.asarray(K.log_weighted_floor(
        jnp.asarray(cnt), jnp.asarray([5000.0]), jnp.ones(cnt.shape, bool),
        5001))
    assert got.astype(np.int64).tolist() == [
        math.floor(int(c) * fractions.Fraction(math.log(5002.0)))
        for c in cnt[0]]
    assert got.max() < 1 << 24
    with pytest.raises(ValueError, match="soft constraints a pod"):
        K.log_weighted_floor(jnp.zeros((33, 4)), jnp.zeros((33,)),
                             jnp.ones((33, 4), bool), 16)


# --------------------------------------- the kernel's score, by hand

ZONES = ("moon-1", "moon-2", "moon-3")


def _blue_pod(name, max_skew=5, key=api.LABEL_ZONE):
    return mkpod(name, labels={"color": "blue"},
                 topology_spread_constraints=[api.TopologySpreadConstraint(
                     max_skew=max_skew, topology_key=key,
                     when_unsatisfiable="ScheduleAnyway",
                     label_selector=api.LabelSelector(
                         match_labels={"color": "blue"}))])


def _zone_world(counts, extra=()):
    """One node a zone holding ``counts`` blue pods each."""
    nodes = [mknode(f"n{i}", labels={api.LABEL_ZONE: z,
                                     api.LABEL_HOSTNAME: f"n{i}"})
             for i, z in enumerate(ZONES)] + list(extra)
    existing = {f"n{i}": [mkpod(f"e{i}-{j}", labels={"color": "blue"})
                          for j in range(c)]
                for i, c in enumerate(counts)}
    return nodes, existing


def _reference_scores(nodes, existing, pod_rec):
    from perfbench.lib import world
    from perfbench.reference import topology_spread_soft as ref
    recs = [world.NodeRec(n.metadata.name, 64000, 1 << 40, 1000,
                          dict(n.metadata.labels)) for n in nodes]
    cluster = ref.Cluster(recs)
    for node, pods in existing.items():
        for p in pods:
            cluster.add(world.PodRec(p.metadata.name, 0, 0, 0,
                                     dict(p.metadata.labels)), node)
    return cluster.spread_score(pod_rec, np.ones(len(recs), bool)).tolist()


def _rec(max_skew=5, key=api.LABEL_ZONE):
    from perfbench.lib import world
    return world.PodRec("p", 0, 0, 0, {"color": "blue"}, spread=(
        (max_skew, key, "ScheduleAnyway", (("color", "blue"),)),))


@pytest.mark.parametrize("what,counts,max_skew,want", [
    # weight log(5): raw int64(7 x 1.609) = 11, 9, 19; the integer
    # quotient 100 x (19 + 9 - s) / 19 = 89 (89.47), 100, 47 (47.37)
    ("raw score, weight log(5), integer quotient", (7, 6, 12), 1,
     [89, 100, 47]),
    # counts under maxSkew read maxSkew - 1 = 4: raw 6, 6, 12
    ("the max-skew adjustment", (0, 3, 8), 5, [100, 100, 50]),
    # maxSkew 1, nothing bound: every raw score 0, max == 0, every node 100
    ("an empty cluster, max == 0", (0, 0, 0), 1, [100, 100, 100]),
    # under maxSkew everywhere: the zones tie at int64(4 x 1.609) = 6
    ("an empty cluster under maxSkew 5", (0, 0, 0), 5, [100, 100, 100]),
])
def test_the_kernels_score_by_hand_and_by_the_reference(what, counts,
                                                        max_skew, want):
    nodes, existing = _zone_world(counts)
    r = run_cluster(nodes, existing, [_blue_pod("p", max_skew)], filters=[],
                    scores=SOFT)
    assert np.asarray(r.scores[0]).tolist() == want, what
    assert _reference_scores(nodes, existing, _rec(max_skew)) == want


def test_a_node_without_the_key_is_ignored_and_its_pods_are_not_counted():
    bare = mknode("bare", labels={api.LABEL_HOSTNAME: "bare"})
    nodes, existing = _zone_world((7, 6, 12), extra=[bare])
    existing["bare"] = [mkpod(f"x{j}", labels={"color": "blue"})
                        for j in range(30)]
    r = run_cluster(nodes, existing, [_blue_pod("p", 1)], filters=[],
                    scores=SOFT)
    assert np.asarray(r.scores[0]).tolist() == [89, 100, 47, 0]
    assert _reference_scores(nodes, existing, _rec(1)) == [89, 100, 47, 0]


def test_a_hostname_constraint_weighs_by_the_scored_nodes():
    """Four nodes scored: the weight is log(6); the node's own count is
    read at Score: raw int64(c x 1.79) = 5, 0, 16, 1 for 3, 0, 9, 1."""
    nodes = [mknode(f"n{i}", labels={api.LABEL_HOSTNAME: f"n{i}"})
             for i in range(4)]
    existing = {f"n{i}": [mkpod(f"e{i}-{j}", labels={"color": "blue"})
                          for j in range(c)]
                for i, c in enumerate((3, 0, 9, 1))}
    want = [100 * (16 + 0 - s) // 16 for s in (5, 0, 16, 1)]
    r = run_cluster(nodes, existing,
                    [_blue_pod("p", 1, api.LABEL_HOSTNAME)], filters=[],
                    scores=SOFT)
    assert np.asarray(r.scores[0]).tolist() == want == [68, 100, 0, 93]
    assert _reference_scores(nodes, existing,
                             _rec(1, api.LABEL_HOSTNAME)) == want
