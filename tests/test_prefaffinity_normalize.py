"""NormalizeScore in ``sp-prefaffinity-5000``: the program's exact integer
quotient against upstream's truncated float64 product, at every ratio the
row can reach.

InterPodAffinity normalises ``int64(100 * (float64(x) / float64(d)))``
upstream (``scoring.go``; the reference keeps it), the program
``_idiv(100 * x, d)``.  The two are known to differ at 29/50, 29/100,
57/100 and 58/100 of the range, where the float64 product falls one ulp
short of the integer (PERF.md, section 7).  In this row a node's raw sum
is twice its red pods, 2c, or 2c + 2k with the k pods the auction
admitted there in earlier rounds; a node holds at most 40 pods by cpu
(4,000m / 100m), the minimum is 0, and the range is 2 x c_max: every
ratio is a / b with 0 <= a <= b <= 40.  None of them is one of the four,
so the difference cannot decide a placement in this cell."""

import numpy as np
import pytest

MAX_PODS_A_NODE = 4000 // 100
KNOWN = {(29, 50), (29, 100), (57, 100), (58, 100)}


def upstream(x: int, d: int) -> int:
    return int(100 * (float(x) / float(d)))


def reachable():
    """(raw, range) over the row: raw = 2a for a pods on the node (bound
    or admitted earlier in the auction), range = 2b for the fullest
    feasible node."""
    return [(2 * a, 2 * b) for b in range(1, MAX_PODS_A_NODE + 1)
            for a in range(0, b + 1)]


def test_upstreams_product_is_the_exact_quotient_at_every_reachable_ratio():
    from kubetpu.ops import kernels as K
    pairs = reachable()
    assert len(pairs) == sum(b + 1 for b in range(1, 41)) == 860
    x = np.array([p[0] for p in pairs], np.float32)
    d = np.array([p[1] for p in pairs], np.float32)
    got = np.asarray(K._idiv(K.MAX_NODE_SCORE * x, d)).astype(np.int64)
    want = np.array([upstream(a, b) for a, b in pairs], np.int64)
    exact = np.array([100 * a // b for a, b in pairs], np.int64)
    assert (got == exact).all()
    assert (want == exact).all()


def test_the_four_known_ratios_are_where_the_two_differ_and_out_of_reach():
    import fractions
    for a, b in KNOWN:
        assert upstream(a, b) == 100 * a // b - 1
    # they are ALL the differences up to a range of 100
    differ = {(a, b) for b in range(1, 101) for a in range(0, b + 1)
              if upstream(a, b) != 100 * a // b}
    assert {fractions.Fraction(a, b) for a, b in differ} \
        == {fractions.Fraction(a, b) for a, b in KNOWN}
    # 29/50 in lowest terms needs a c_max that is a multiple of 50, the
    # other three a multiple of 100: no c / c_max with c_max <= 40
    # reduces to any of them
    reach = {fractions.Fraction(a, b) for a, b in reachable()}
    assert not reach & {fractions.Fraction(a, b) for a, b in KNOWN}


@pytest.mark.parametrize("x,d", sorted(KNOWN))
def test_the_program_keeps_the_exact_quotient_where_upstream_falls_short(x, d):
    """What the row cannot reach stays as PERF.md, section 7 has it: the
    program reads one higher there (the cure is ROADMAP's, not this
    row's)."""
    from kubetpu.ops import kernels as K
    got = int(K._idiv(K.MAX_NODE_SCORE * np.float32(x), np.float32(d)))
    assert got == 100 * x // d == upstream(x, d) + 1
