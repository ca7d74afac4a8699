"""Unit + property tests for bitset schema metadata and attribute maps."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import schema as sc


def test_bitset_paper_notation():
    b = sc.Bitset.from_string("10011")
    assert str(b) == "10011"
    assert b.popcount() == 3
    assert b.indices().tolist() == [0, 3, 4]
    assert b.test(0) and not b.test(1) and b.test(4)


def test_rank_select_inverse():
    b = sc.Bitset.from_string("1010110")
    for i in range(b.n):
        if b.test(i):
            assert b.select(b.rank(i)) == i


def test_map_vr_paper_example():
    # Table VI: bitset 10011 -> 2nd and 3rd attrs dropped (0-based: 1, 2)
    b = sc.Bitset.from_string("10011")
    assert sc.map_vr_f(b, 0) == 0
    assert sc.map_vr_f(b, 1) is None
    assert sc.map_vr_f(b, 3) == 1
    assert sc.map_vr_f(b, 4) == 2
    assert sc.map_vr_b(b, 0) == 0
    assert sc.map_vr_b(b, 1) == 3
    assert sc.map_vr_b(b, 2) == 4


def test_map_va_paper_example():
    # Table VI: 101011 with m=4 -> attrs 0,2 engineered the two new attrs
    b = sc.Bitset.from_string("101011")
    m = 4
    assert sc.map_va_f(m, 2) == 2
    assert sc.map_va_b(b, m, 1) == [1]            # preserved position
    assert sc.map_va_b(b, m, 4) == [0, 2]         # new attr -> source attrs
    assert sc.map_va_b(b, m, 5) == [0, 2]


def test_map_join_paper_example():
    # Table VI: [10101, 11010] over a 5-attr output
    bl = sc.Bitset.from_string("10101")
    br = sc.Bitset.from_string("11010")
    # forward: left attr 0 -> out 0; left attr 1 -> out 2; left attr 2 -> out 4
    assert sc.map_join_f(bl, 0) == 0
    assert sc.map_join_f(bl, 1) == 2
    assert sc.map_join_f(bl, 2) == 4
    # backward: out attr 1 comes from the right dataset only
    assert sc.map_join_b(bl, 1) is None
    assert sc.map_join_b(br, 1) == 1
    assert sc.map_join_b(br, 4) is None


def test_perm_fallback():
    # paper: [4,2,5] (1-based) = order-changing vertical reduction
    perm = np.array([3, 1, 4])
    assert sc.perm_backward(perm, 0) == 3
    assert sc.perm_forward(perm, 1) == 1
    assert sc.perm_forward(perm, 0) is None


# ---------------------------------------------------------------------------
# Property tests (hypothesis): rank/select laws over arbitrary bitsets
# ---------------------------------------------------------------------------
@given(st.lists(st.booleans(), min_size=1, max_size=200))
@settings(max_examples=200, deadline=None)
def test_rank_is_cumsum(bits):
    b = sc.Bitset.from_bits(bits)
    cum = np.cumsum(np.asarray(bits, dtype=int))
    for i in range(len(bits)):
        assert b.rank(i) == cum[i]


@given(st.lists(st.booleans(), min_size=1, max_size=120))
@settings(max_examples=100, deadline=None)
def test_vr_forward_backward_roundtrip(bits):
    b = sc.Bitset.from_bits(bits)
    for i in range(len(bits)):
        j = sc.map_vr_f(b, i)
        if bits[i]:
            assert j is not None and sc.map_vr_b(b, j) == i
        else:
            assert j is None


@given(st.lists(st.booleans(), min_size=1, max_size=120).filter(lambda x: any(x)))
@settings(max_examples=100, deadline=None)
def test_join_maps_are_partial_inverses(bits):
    b = sc.Bitset.from_bits(bits)
    n_in = b.popcount()
    for i in range(n_in):
        j = sc.map_join_f(b, i)
        assert j is not None and sc.map_join_b(b, j) == i
    for j in range(len(bits)):
        a = sc.map_join_b(b, j)
        if bits[j]:
            assert sc.map_join_f(b, a) == j
        else:
            assert a is None
