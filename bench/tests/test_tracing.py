"""The reduction from trace to per-layer metrics, on a synthetic reduced
trace whose answers are known."""

import pytest

from bench import cells, tracing

DEV = "/device:TPU:0"
#: two requests: compress [0, 10] and [20, 30], decompress after each
TRACE = {
    "device_ops": [
        ["fusion.1", 0.5, 0.5, DEV], ["fusion.2", 0.8, 0.4, DEV],  # union [0.5, 1.2]
        ["copy.3", 21.0, 1.0, DEV], ["fusion.1", 40.0, 1.0, DEV],  # the last one outside
    ],
    "spans": [
        ["compress", 0.0, 10.0, "python"], ["decompress", 10.0, 5.0, "python"],
        ["compress", 20.0, 10.0, "python"], ["decompress", 30.0, 5.0, "python"],
        ["host_encode.sz", 2.0, 6.0, "worker/1"], ["host_encode.zfp", 3.0, 7.0, "worker/2"],
        ["host_encode.sz", 24.0, 5.0, "worker/1"],
        ["host_decode.sz", 10.5, 4.0, "worker/1"], ["host_decode.sz", 30.5, 4.0, "worker/1"],
    ],
}
RECORDS = [{"raw_bytes": 5 * 10**8}, {"raw_bytes": 5 * 10**8}]


def read(metric):
    return cells.load("hurricane-isabel.eb1e-4").reader(metric)(TRACE, RECORDS)


def test_interval_helpers():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.overlap([(0, 2), (3, 4)], 1, 3.5) == pytest.approx(1.5)
    assert tracing.complement([(0, 2), (3, 4)], -1, 5) == [(-1, 0), (2, 3), (4, 5)]
    assert tracing.complement([(0, 2)], 0.5, 1.5) == []


def test_device_idle_share_of_compress():
    # busy inside compress: 0.7 s + 1.0 s of 20 s
    assert read("compress.device_idle_pct") == pytest.approx(100 * (1 - 1.7 / 20))


def test_select_share_before_the_first_encode():
    # 2 s of the first request and 4 s of the second, of 20 s
    assert read("compress.select_pct") == pytest.approx(100 * 6 / 20)


def test_span_sums_per_raw_gb():
    assert read("host_encode.s_per_GB") == pytest.approx((6 + 7 + 5) / 1.0)
    assert read("host_decode.s_per_GB") == pytest.approx(8 / 1.0)


def test_idle_gaps_are_named_by_the_span_that_covers_them():
    gaps = tracing.idle_gaps(TRACE)
    # first request: [0, 0.5] and [1.2, 10]; second: [20, 21] and [22, 30]
    assert [round(g, 6) for _, g in gaps] == [8.8, 8.0, 1.0, 0.5]
    assert [n for n, _ in gaps] == ["host_encode.zfp", "host_encode.sz", "compress", "compress"]


def test_window_busy_and_top_ops():
    assert tracing.window(TRACE) == (0.0, 35.0)
    assert tracing.busy_seconds(TRACE) == pytest.approx(1.7)
    assert tracing.top_device_ops(TRACE)[0] == ("fusion.1", 1.5)


def test_readers_return_nothing_without_their_spans():
    empty = {"device_ops": [], "spans": []}
    for m in cells.load("hurricane-isabel.eb1e-4").per_layer:
        assert cells.load("hurricane-isabel.eb1e-4").reader(m["name"])(empty, RECORDS) is None
