"""The reduction on a trace recorded on one v5e chip: one request of the
`cesm-atm.eb1e-4` cell (8 fields of 1800x3600, all SZ), with the codec
proxies installed."""

import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import cells, tracing

TRACE = Path(__file__).parent / "data" / "cesm-atm.eb1e-4.xplane.pb.gz"
RAW_BYTES = 8 * 1800 * 3600 * 4


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    with gzip.open(TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tracing.reduce_xplane(path)


def read(metric, trace):
    return cells.load("hurricane-isabel.eb1e-4").reader(metric)(trace, [{"raw_bytes": RAW_BYTES}])


def test_spans_and_device_ops_found(trace):
    names = [n for n, *_ in trace["spans"]]
    assert names.count("compress") == names.count("decompress") == 1
    assert names.count("host_encode.sz") == names.count("host_decode.sz") == 8
    assert {d for *_, d in trace["device_ops"]} == {"/device:TPU:0"}
    assert len(trace["device_ops"]) > 100


def test_idle_share_against_a_microsecond_grid(trace):
    (a, b), = tracing.spans(trace, "compress")
    grid = np.zeros(int((b - a) * 1e6) + 1, bool)
    for _, s, d, _ in trace["device_ops"]:
        lo, hi = max(s, a), min(s + d, b)
        if hi > lo:
            grid[int((lo - a) * 1e6):int(np.ceil((hi - a) * 1e6))] = True
    assert read("compress.device_idle_pct", trace) == pytest.approx(
        100 * (1 - grid.mean()), abs=0.05)


def test_select_share_and_span_sums(trace):
    (a, b), = tracing.spans(trace, "compress")
    first = min(s for n, s, _, _ in trace["spans"] if n.startswith("host_encode."))
    assert read("compress.select_pct", trace) == pytest.approx(100 * (first - a) / (b - a))
    for kind in ("encode", "decode"):
        total = sum(d for n, _, d, _ in trace["spans"] if n == f"host_{kind}.sz")
        assert read(f"host_{kind}.s_per_GB", trace) == pytest.approx(total / (RAW_BYTES / 1e9))


def test_gaps_labelled_by_the_covering_span(trace):
    gaps = tracing.idle_gaps(trace)
    (a, b), = tracing.spans(trace, "compress")
    assert sum(g for _, g in gaps) == pytest.approx(
        (b - a) * read("compress.device_idle_pct", trace) / 100, rel=1e-6)
    assert min(g for _, g in gaps) >= 1e-6
    # the longest gap is the host SZ coder's; the selection before it is
    # device-idle time with no codec span over it
    assert gaps[0][0] == "host_encode.sz" and gaps[0][1] > 5.0
    assert {n for n, _ in gaps} == {"host_encode.sz", "compress"}
