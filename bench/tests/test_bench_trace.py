"""The trace reduction (lib/trace.py) on synthetic event lists."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from lib import trace  # noqa: E402


def test_union_merges_overlaps_and_drops_empty():
    assert trace.union([(3, 4), (0, 1), (0.5, 2), (5, 5)]) == [(0, 2), (3, 4)]
    assert trace.length(trace.union([(0, 2), (1, 3), (10, 11)])) == 4


def test_subtract_and_gaps():
    a = [(0, 10)]
    b = [(1, 2), (4, 6), (9, 12)]
    assert trace.subtract(a, b) == [(0, 1), (2, 4), (6, 9)]
    assert trace.gaps([(1, 2), (4, 6)], 0, 8) == [(0, 1), (2, 4), (6, 8)]


def test_reduce_idle_share_exposed_collective_and_gap_labels():
    # two devices, window [0, 10]: device a busy 0-4 (compute) and 3-6
    # (all-reduce, 4-6 exposed); device b busy 0-8 with an all-gather
    # wholly under compute. Events outside the window are clipped.
    devices = {
        "a": [(0, 4, "%fusion.1 = f32[8] fusion(...)"),
              (3, 6, "%all-reduce-start.2 = bf16[4] all-reduce-start(...)"),
              (-5, 0, "%fusion.0 = f32[8] fusion(...)")],
        "b": [(0, 8, "%fusion.1 = f32[8] fusion(...)"),
              (2, 3, "%all-gather.1 = s32[4] all-gather(...)")],
    }
    host = [(0, 10, "bench.window"), (6, 10, "bench.callback"),
            (7, 8.5, "$array.py _value")]
    red = trace.reduce(devices, host, (0, 10))
    assert red["window_s"] == 10
    assert red["busy_s"] == pytest.approx((6 + 8) / 2)
    assert red["idle_share"] == pytest.approx(1 - 7 / 10)
    assert red["collective_s"] == pytest.approx((3 + 1) / 2)
    assert red["exposed_collective_s"] == pytest.approx((2 + 0) / 2)
    # gaps: a idles 6-10 (midpoint 8: the innermost span there is
    # "$array.py _value"), b idles 8-10 (midpoint 9: "bench.callback")
    gaps = dict(red["idle_gaps"])
    assert gaps == pytest.approx({"$array.py _value": 4 / 2,
                                  "bench.callback": 2 / 2})
    ops = dict(red["device_ops"])
    assert ops["%fusion.1 = f32[8] fusion(...)"] == pytest.approx((4 + 8) / 2)


def test_short_name_strips_layouts():
    name = ("%fusion.61 = (bf16[800000,512]{1,0:T(8,128)(2,1)}, "
            "f32[800000,512]{1,0:T(8,128)}) fusion(...)")
    assert trace.short_name(name) == \
        "%fusion.61 = (bf16[800000,512], f32[800000,512]) fusion(...)"


def test_collectives_are_told_by_their_own_opcode():
    assert trace.is_collective(
        "%all-reduce-start.2 = (bf16[4]{0}, bf16[4]{0}) all-reduce-start("
        "bf16[4]{0} %x), replica_groups={{0,1,2,3}}")
    assert trace.is_collective("%all-gather.1 = s32[4]{0} all-gather(...)")
    # a fusion that reads a collective's result is compute
    assert not trace.is_collective(
        "%fusion.5 = f32[8]{0:T(128)} fusion(f32[8]{0} %all-reduce.3), "
        "kind=kLoop")
    assert not trace.is_collective(
        "%copy-start.13 = (bf16[1,512]{1,0}, bf16[1,512]{1,0}, u32[]) "
        "copy-start(bf16[1,512]{1,0} %all-gather.2)")
    assert trace.is_collective("all-reduce.7")


def test_find_span_takes_the_last():
    host = [(0, 1, "bench.window"), (5, 9, "bench.window"), (2, 3, "x")]
    assert trace.find_span(host, "bench.window") == (5, 9)
    with pytest.raises(KeyError):
        trace.find_span(host, "missing")
