"""The program's spans in a reduced trace: the readers of `bench/program_spans.py`
on a synthetic trace whose answers are known, and the reduction on traces
recorded on one v5e chip."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import tracing

DEV = "/device:TPU:0"
DATA = Path(__file__).parent / "data"
CALLER, A, B = "1:python3", "2:python3", "3:python3"
#: one request: compress_pytree [0, 10], decompress_pytree [10, 15]; two
#: fields, "u" (SZ) on thread A and "v" (ZFP) on thread B
TRACE = {
    "device_ops": [["fusion.1", 0.9, 0.2, DEV], ["copy.2", 1.25, 0.05, DEV]],
    "spans": [["compress", 0.0, 10.0, "python3"], ["decompress", 10.0, 5.0, "python3"]],
    "program_spans": [
        ["repro.compress_pytree", 0.0, 10.0, CALLER, {"request": 1, "fields": 2,
                                                      "raw_bytes": 10**9}],
        ["repro.compress.materialize", 0.1, 0.2, CALLER, {"fields": 2, "bytes": 10**9}],
        ["repro.compress.gather", 0.3, 0.5, CALLER, {"fields": 2, "blocks": 100}],
        ["repro.compress.estimate", 0.8, 0.4, CALLER, {"fields": 2, "n_blocks": 128}],
        ["repro.encode", 1.2, 8.0, A, {"request": 1, "field": "u", "codec": "sz",
                                       "raw_bytes": 5 * 10**8}],
        ["repro.sz.quantize", 1.3, 1.0, A, {}],
        ["repro.sz.table", 2.3, 2.0, A, {"symbols": 125 * 10**6}],
        ["repro.sz.pack", 4.3, 4.0, A, {}],
        ["repro.sz.container", 8.3, 0.5, A, {"outliers": 0}],
        ["repro.encode", 1.2, 3.0, B, {"request": 1, "field": "v", "codec": "zfp",
                                       "raw_bytes": 5 * 10**8}],
        ["repro.zfp.blockize", 1.2, 0.1, B, {}],
        ["repro.decompress_pytree", 10.0, 5.0, CALLER, {"request": 2, "fields": 2}],
        ["repro.decode", 10.1, 4.0, A, {"request": 2, "field": "u", "codec": "sz",
                                        "raw_bytes": 5 * 10**8}],
        ["repro.sz.unpack", 10.1, 3.0, A, {}],
        ["repro.sz.reconstruct", 13.1, 1.0, A, {}],
        ["repro.decode", 10.1, 2.0, B, {"request": 2, "field": "v", "codec": "zfp",
                                        "raw_bytes": 5 * 10**8}],
    ],
    "device_modules": [["jit_select_estimate_batched", 0.9, 0.2, DEV],
                       ["jit_reshape", 1.25, 0.05, DEV]],
}

EXPECTED = {
    "compress.materialize_pct": 2.0,  # 0.2 s of 10 s
    "compress.gather_pct": 5.0,
    "compress.estimate_pct": 4.0,
    "compress.single_field_pct": 50.0,  # only "u" is open over [4.2, 9.2]
    "sz_encode.predict_s_per_GB": 2.0,  # 1 s over the 0.5 GB of SZ fields
    "sz_encode.huffman_s_per_GB": 12.0,  # (2 + 4) s
    "sz_decode.huffman_s_per_GB": 6.0,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_known_answer(metric):
    assert ps.METRICS[metric](TRACE, []) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_returns_nothing_without_its_spans(metric):
    assert ps.METRICS[metric]({"device_ops": [], "spans": TRACE["spans"]}, []) is None
    assert ps.METRICS[metric]({"device_ops": [], "spans": [], "program_spans": []}, []) is None
    # the request spans alone are not enough either
    requests = [r for r in TRACE["program_spans"] if r[0] in ps.REQUEST_SPANS]
    assert ps.METRICS[metric]({"program_spans": requests}, []) is None


def test_single_field_counts_only_its_own_request():
    other = ["repro.encode", 5.0, 1.0, "4:python3", {"request": 9, "field": "w",
                                                      "codec": "sz", "raw_bytes": 1}]
    trace = dict(TRACE, program_spans=TRACE["program_spans"] + [other])
    assert ps.METRICS["compress.single_field_pct"](trace, []) == pytest.approx(50.0)


def test_program_idle_gaps_named_by_the_innermost_span():
    gaps = ps.program_idle_gaps(TRACE)
    # compress: [0, 0.9], [1.1, 1.25], [1.3, 10]; decompress: [10, 15]
    assert sum(g for _, g in gaps) == pytest.approx(0.9 + 0.15 + 8.7 + 5.0)
    assert [n for n, _ in gaps[:2]] == ["repro.sz.pack", "repro.sz.table"]
    assert gaps[0][1] == pytest.approx(4.0) and gaps[1][1] == pytest.approx(2.0)
    named = {}
    for n, g in gaps:
        named.setdefault(n, []).append(round(g, 9))
    assert named["repro.compress_pytree"] == [0.8, 0.1]  # [9.2, 10], [0, 0.1]
    assert named["repro.compress.estimate"] == [0.1, 0.1]  # around the device op
    assert named["repro.zfp.blockize"] == [0.05]
    # thread B's decode is the shortest span over [10.1, 12.1]
    assert named["repro.decode"] == [2.0]
    assert named["repro.encode"] == [0.4]  # [8.8, 9.2]: no coder span open
    assert ps.program_idle_gaps({"device_ops": [], "spans": []}) == []


def test_idle_covered_share():
    # 9.75 s idle in compress, 0.9 s of it under the request span alone
    assert ps.idle_covered_pct(TRACE) == pytest.approx(100 * (1 - 0.9 / 9.75))
    assert ps.idle_covered_pct({"device_ops": [], "spans": []}) is None


def test_spans_per_request_and_module_seconds():
    assert ps.spans_per_request(TRACE) == 16
    assert ps.module_seconds(TRACE) == [("jit_select_estimate_batched", 0.2),
                                        ("jit_reshape", 0.05)]
    assert ps.module_name("jit_f(16733318141006235249)") == "jit_f"


def _reduced(name, tmp_path):
    path = tmp_path / "t.xplane.pb"
    with gzip.open(DATA / name) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def test_recorded_trace_without_program_spans(tmp_path):
    """The trace recorded before the program had spans: the harness's part
    of the reduction is unchanged, and no program span is found."""
    path = _reduced("cesm-atm.eb1e-4.xplane.pb.gz", tmp_path)
    trace = ps.reduce_xplane(path)
    plain = tracing.reduce_xplane(path)
    assert trace["spans"] == plain["spans"] and trace["device_ops"] == plain["device_ops"]
    assert trace["program_spans"] == []
    assert {n for n, *_ in trace["device_modules"]} == {"jit_f"}
    for read in ps.METRICS.values():
        assert read(trace, [{"raw_bytes": 1}]) is None


@pytest.fixture(scope="module")
def hurricane(tmp_path_factory):
    """One request of `hurricane-isabel.eb1e-4` (4 fields of 100x500x500,
    3 SZ + 1 ZFP), recorded on one v5e by `bench/program_spans.py
    --record` with the codec proxies installed."""
    path = _reduced("hurricane-isabel.eb1e-4.xplane.pb.gz", tmp_path_factory.mktemp("h"))
    return ps.reduce_xplane(path)


def test_recorded_request_spans(hurricane):
    names = [n for n, *_ in hurricane["program_spans"]]
    for name in ps.REQUEST_SPANS:
        assert names.count(name) == 1
    encode = ps.program_spans(hurricane, "repro.encode")
    assert sorted(a["codec"] for *_, a in encode) == ["sz", "sz", "sz", "zfp"]
    assert {a["raw_bytes"] for *_, a in encode} == {100 * 10**6}
    assert len(names) == 37
    for name, read in ps.METRICS.items():
        assert read(hurricane, []) is not None, name
    # the harness's spans are still found beside the program's
    assert [n for n, *_ in hurricane["spans"]].count("host_encode.sz") == 3


def test_device_clock_agrees_with_the_host_clock(hurricane):
    """The estimator program runs on the device inside the host span that
    launches it and waits for its outputs."""
    (s, e, _), = ps.program_spans(hurricane, "repro.compress.estimate")
    (start, dur), = [(st, d) for m, st, d, _ in hurricane["device_modules"]
                     if m == "jit_select_estimate_batched"]
    assert s < start and start + dur < e


def test_recorded_idle_time_named_by_child_spans(hurricane):
    assert ps.idle_covered_pct(hurricane) > 95.0
    assert ps.program_idle_gaps(hurricane)[0][0].startswith(("repro.sz.", "repro.zfp."))
