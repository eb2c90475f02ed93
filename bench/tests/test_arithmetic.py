"""Rates and ratio from synthetic timings and streams."""

import pytest

from bench import cells, run
from bench.system import Stream, codec_split, raw_bytes, stream_bytes


def _cell():
    return cells.load("hurricane-isabel.eb1e-4")


def test_rates_are_sums_over_the_window():
    records = [
        dict(compress_s=2.0, decompress_s=1.0, raw_bytes=10**9, stream_bytes=4 * 10**8),
        dict(compress_s=6.0, decompress_s=3.0, raw_bytes=10**9, stream_bytes=4 * 10**8),
    ]
    m = run.end_to_end(_cell(), records, setup_s=12.5)
    # 2 GB over 8 s and 4 s, not the median of 1/2 and 1/6 GB/s
    assert m["compress_GBps"] == {"value": pytest.approx(0.25), "unit": "GB/s"}
    assert m["decompress_GBps"] == {"value": pytest.approx(0.5), "unit": "GB/s"}
    assert m["ratio"] == {"value": pytest.approx(2.5), "unit": "x"}
    assert m["setup_s"] == {"value": 12.5, "unit": "s"}
    assert list(m) == [e["name"] for e in _cell().end_to_end]


def test_stream_and_raw_byte_counts():
    import numpy as np

    streams = [Stream("a", "sz", b"x" * 10, (4, 4), "float32", 1.0),
               Stream("b", "zfp", b"y" * 7, (4, 4), "float32", 1.0),
               Stream("c", "sz", b"", (4, 4), "float32", 1.0)]
    assert stream_bytes(streams) == 17
    assert codec_split(streams) == {"sz": 2, "zfp": 1}
    snap = {"a": np.zeros((4, 4), np.float32), "b": np.zeros((2, 3), np.float32)}
    assert raw_bytes(snap) == 4 * 22


def test_decode_cpu_seconds_per_gb():
    records = [dict(decompress_cpu_s=3.0, raw_bytes=10**9),
               dict(decompress_cpu_s=5.0, raw_bytes=10**9)]
    read = _cell().reader("host_decode.cpu_s_per_GB")
    assert read({}, records) == pytest.approx(4.0)
    assert read({}, []) is None
