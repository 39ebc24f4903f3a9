"""The trace reduction on a small recorded trace: device busy time as the
union of op intervals, idle gaps named by the host span around them,
kernel time by name, self time of nested ops."""
from __future__ import annotations

import pytest

import bench_chip_util  # noqa: F401  (puts benchmarks/chip on the path)
import trace_reduce as tr

# one chip, times in ns: a decode-like step inside a while loop, a gap
# while the host samples, then a second step; the window span covers both
TRACE = {
    "device": {0: [
        (1_000, 9_000, "while.13"),
        (1_000, 4_000, "decode_attention.4"),
        (4_000, 6_000, "fusion.100"),
        (5_000, 5_500, "copy.87"),
        (6_000, 9_000, "decode_attention.4"),
        (12_000, 16_000, "while.13"),
        (12_000, 14_000, "decode_attention.4"),
    ]},
    "host": [
        (0, 20_000, "bench.window"),
        (9_000, 12_000, "bench.step"),
        (10_000, 11_000, "bench.sample"),
        (16_000, 20_000, "bench.step"),
    ],
}


def test_busy_is_the_union_of_op_intervals():
    red = tr.reduce(TRACE, {"decode_attention": "decode_attention"})
    # busy [1,9] and [12,16] us of a 20 us window
    assert red["busy_s"] == pytest.approx(12_000 / 1e9)
    assert red["window_s"] == pytest.approx(20_000 / 1e9)
    assert red["devices"] == 1


def test_idle_gaps_are_named_by_the_innermost_host_span():
    red = tr.reduce(TRACE)
    gaps = dict(red["idle_gaps"])
    # [0,1] before any span but the window, [9,12] mid-sampling (the
    # gap's midpoint 10.5 us lies in bench.sample), [16,20] in a step
    assert gaps["no bench span"] == pytest.approx(1_000 / 1e9)
    assert gaps["bench.sample"] == pytest.approx(3_000 / 1e9)
    assert gaps["bench.step"] == pytest.approx(4_000 / 1e9)


def test_kernel_time_and_calls_by_name():
    red = tr.reduce(TRACE, {"decode_attention": "decode_attention"})
    k = red["kernels"]["decode_attention"]
    assert k["calls"] == 3
    assert k["s"] == pytest.approx(8_000 / 1e9)


def test_self_time_leaves_out_nested_ops():
    selfs = tr.self_times(TRACE["device"][0])
    assert selfs["while.13"] == pytest.approx(0 + 2_000)
    assert selfs["fusion.100"] == pytest.approx(1_500)
    assert selfs["copy.87"] == pytest.approx(500)
    assert sum(selfs.values()) == pytest.approx(12_000)


def test_busy_is_averaged_over_chips_and_clipped_to_the_window():
    two = {"device": {0: [(0, 10_000, "a")], 1: [(5_000, 30_000, "b")]},
           "host": [(0, 20_000, "bench.window")]}
    red = tr.reduce(two)
    assert red["busy_s"] == pytest.approx((10_000 + 15_000) / 2 / 1e9)


def test_no_device_plane_reads_nothing():
    assert tr.reduce({"device": {}, "host": []}) is None


def test_op_name_strips_the_hlo_text():
    assert tr.op_name("%decode_attention.4 = bf16[4,32,1,96] custom-call()"
                      ) == "decode_attention.4"
