"""The trace reduction, on intervals worked out by hand."""
import pytest

from chipbench import trace as T


def test_union_gaps_and_cover():
    merged = T.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 11)])
    assert merged == [[0, 3], [5, 9], [10, 11]]
    assert T.gaps(merged, 1, 12) == [(3, 5), (9, 10), (11, 12)]
    cov = T.Covered(merged)
    assert cov(0, 12) == 3 + 4 + 1
    assert cov(2, 6) == 1 + 1
    assert cov(3, 5) == 0


def test_reduce_by_hand():
    # two devices; window [0, 10]; spans a: [0, 4] and [6, 8], b: [4, 6]
    tr = T.Trace(
        devices={"/device:TPU:0": [("dot", 1, 3), ("dot", 6.5, 7.5),
                                   ("copy", 9, 12)],
                 "/device:TPU:1": [("dot", 1, 2)],
                 # held, not used in the window: not averaged over
                 "/device:TPU:2": [("dot", 10.5, 11)],
                 "/device:TPU:3": []},
        spans=[("cb.window", 0, 10), ("cb.a", 0, 4), ("cb.b", 4, 6),
               ("cb.a", 6, 8)])
    r = T.reduce(tr)
    assert r["window_s"] == 10 and r["devices"] == 2
    assert r["busy_s"] == pytest.approx((2 + 1 + 1 + 1) / 2)
    assert r["span_device_s"]["a"] == pytest.approx((2 + 1 + 1) / 2)
    assert r["span_device_s"]["b"] == 0
    assert r["span_count"]["a"] == 2
    assert r["device_ops"][0] == ["dot", pytest.approx((2 + 1 + 1) / 2)]
    # device 0's idle stretches, longest first: [3, 6.5] (middle in b),
    # [7.5, 9] (middle 8.25, after a's second span), [0, 1] (in a)
    assert r["idle_gaps"] == [["b", 3.5], ["outside_spans", 1.5],
                              ["a", 1.0]]


def test_op_name_drops_layouts_and_comments():
    text = ("%while.9 = (s32[]{:T(128)}, s32[16,73]{1,0:T(8,128)S(1)}, "
            "/*index=2*/pred[16,64,73]{2,1,0:T(8,128)(4,1)S(1)}) while(...)")
    assert T.op_name(text) == ("%while.9 = (s32[], s32[16,73], "
                               "pred[16,64,73]) while(...)")
    assert len(T.op_name("%fusion.1 = " + "s32[16], " * 40)) == T.NAME_LEN
