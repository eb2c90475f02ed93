"""The program's own spans, read from the profiler trace the harness records.

`repro.core` opens `jax.profiler.TraceAnnotation` spans named `repro.*`
inside `compress_pytree`, `decompress_pytree` and the host coders (PERF.md
§3, "Spans and counters"); they land in the same trace as the device ops,
on the same clock, and only while a trace is being recorded.
`tracing.reduce_xplane` keeps the harness's own spans; `reduce_xplane` here
returns the same dict with two lists added beside them, from the same file:

* "program_spans": [[name, start, dur, thread, args]] for every `repro.*`
  event on a host plane. `thread` is "<index in the plane>:<line name>",
  since the TPU host names many threads "python3"; `args` holds the span's
  keyword args (`request`, `field`, `codec`, `raw_bytes`, ...).
* "device_modules": [[name, start, dur, device]] from each device plane's
  "XLA Modules" line, the "(hash)" suffix stripped: which program ran when.

`program_idle_gaps` names the device-idle time inside the request spans by
the innermost program span over it. `METRICS` holds the readers of the
per-layer numbers these spans give, `read(trace, records)` each, None where
the trace has no such spans (a program that opens none, a trace reduced by
`tracing.reduce_xplane` alone).

Run as a script, it measures one cell's spans on the chip:

    python3 bench/program_spans.py --workload <cell> --seed <n> [--requests 2]
        [--record <file.xplane.pb.gz>]

After the set-up `bench/run.py` makes (snapshot, one warm-up request), it
runs `--requests` pairs of requests, one untraced then one traced with the
codec proxies installed, each in a profiler trace of its own. It prints a
JSON line: the traced and untraced compress and decompress rates (what
tracing costs), and per traced request the readings of `METRICS` and of
the cell's own per-layer metrics, the ten longest `program_idle_gaps`, the
share of device-idle time inside `repro.compress_pytree` that a child span
covers, and the spans per request. `--record` keeps the first traced
request's trace, gzipped.
"""

from __future__ import annotations

import re
import sys
import warnings
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench import tracing  # noqa: E402

PREFIX = "repro."
#: the program spans around whole requests
REQUEST_SPANS = ("repro.compress_pytree", "repro.decompress_pytree")
#: the device plane's line of whole programs
MODULE_LINE = "XLA Modules"
_HASH = re.compile(r"\(\d+\)$")


def module_name(name: str) -> str:
    """"jit_select_estimate_batched" for "jit_select_estimate_batched(123)"."""
    return _HASH.sub("", name)


def reduce_xplane(path) -> dict:
    """`tracing.reduce_xplane(path)` plus "program_spans" and "device_modules"."""
    from jax.profiler import ProfileData

    trace = tracing.reduce_xplane(path)
    program, modules = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    modules += [[module_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                 plane.name] for e in line.events]
        elif plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        program.append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                        f"{k}:{line.name}", _args(e)])
    trace["program_spans"] = program
    trace["device_modules"] = modules
    return trace


def _args(event) -> dict:
    # the stats iterator's type warns that it has no __module__; a warning
    # raised as an error inside the iterator would abort the process
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(event.stats)


def program_spans(trace: dict, name: str) -> list[tuple[float, float, dict]]:
    """(start, end, args) of the program spans called `name`, by start."""
    return sorted(((s, s + d, a) for n, s, d, _, a in trace.get("program_spans", ())
                   if n == name), key=lambda t: t[0])


def seconds(trace: dict, name: str) -> float:
    return sum(e - s for s, e, _ in program_spans(trace, name))


def program_idle_gaps(trace: dict, within: tuple[str, ...] = REQUEST_SPANS,
                      shortest: float = 1e-6) -> list[tuple[str, float]]:
    """Device-idle time inside the `within` request spans, longest first,
    in stretches named by the innermost program span over them: each idle
    gap is cut where a program span opens or closes, each piece takes the
    name of the shortest span over it on any thread (the request span's
    own name where no other is), and neighbouring pieces of one name merge.
    Gaps shorter than `shortest` are the rounding between device ops."""
    busy = tracing.device_busy(trace)
    dev_union = tracing.union(iv for ivs in busy.values() for iv in ivs)
    rows = [(n, s, s + d) for n, s, d, _, _ in trace.get("program_spans", ())]
    out = []
    for req in within:
        for a, b, _ in program_spans(trace, req):
            inner = [r for r in rows if r[1] < b and r[2] > a]
            for g0, g1 in tracing.complement(dev_union, a, b):
                if g1 - g0 < shortest:
                    continue
                cuts = sorted({g0, g1} | {t for _, s, e in inner for t in (s, e) if g0 < t < g1})
                label, length = None, 0.0
                for p0, p1 in zip(cuts, cuts[1:]):
                    over = [(e - s, n) for n, s, e in inner if s <= p0 and e >= p1]
                    name = min(over)[1] if over else req
                    if label is not None and name != label:
                        out.append((label, length))
                        length = 0.0
                    label, length = name, length + (p1 - p0)
                out.append((label, length))
    return sorted(out, key=lambda t: -t[1])


def idle_covered_pct(trace: dict) -> float | None:
    """Share of the device-idle time inside `repro.compress_pytree` spans
    that a program span other than the request span covers."""
    req = REQUEST_SPANS[0]
    gaps = program_idle_gaps(trace, within=(req,), shortest=0.0)
    idle = sum(g for _, g in gaps)
    if not idle:
        return None
    return 100.0 * (1.0 - sum(g for n, g in gaps if n == req) / idle)


# ---------------------------------------------------------------------------
# readers: read(trace, records) -> number or None
# ---------------------------------------------------------------------------


def _share_of_compress(child: str):
    def read(trace, records):
        total = seconds(trace, "repro.compress_pytree")
        if not total or not program_spans(trace, child):
            return None
        return 100.0 * seconds(trace, child) / total
    read.__doc__ = f"`{child}` seconds / `repro.compress_pytree` seconds, in %."
    return read


def single_field_pct(trace, records):
    """Share of each `repro.compress_pytree` span during which exactly one
    `repro.encode` of its request is open: the pool running one field."""
    total, single = 0.0, 0.0
    for a, b, args in program_spans(trace, "repro.compress_pytree"):
        total += b - a
        edges = []
        for s, e, eargs in program_spans(trace, "repro.encode"):
            if eargs.get("request") == args.get("request") and s < b and e > a:
                edges += [(max(s, a), 1), (min(e, b), -1)]
        edges.sort()
        open_, last = 0, a
        for t, step in edges:
            if open_ == 1:
                single += t - last
            open_, last = open_ + step, t
    if not total or not program_spans(trace, "repro.encode"):
        return None
    return 100.0 * single / total


def _sz_raw_gb(trace, half: str) -> float:
    return sum(a.get("raw_bytes", 0) for _, _, a in program_spans(trace, f"repro.{half}")
               if a.get("codec") == "sz") / 1e9


def _sz_s_per_gb(half: str, stages: tuple[str, ...]):
    def read(trace, records):
        gb = _sz_raw_gb(trace, half)
        if not gb or not any(program_spans(trace, s) for s in stages):
            return None
        return sum(seconds(trace, s) for s in stages) / gb
    read.__doc__ = (f"{' + '.join(stages)} seconds / raw GB of the `repro.{half}` "
                    "spans with codec sz.")
    return read


METRICS = {
    "compress.materialize_pct": _share_of_compress("repro.compress.materialize"),
    "compress.gather_pct": _share_of_compress("repro.compress.gather"),
    "compress.estimate_pct": _share_of_compress("repro.compress.estimate"),
    "compress.single_field_pct": single_field_pct,
    "sz_encode.predict_s_per_GB": _sz_s_per_gb("encode", ("repro.sz.quantize",)),
    "sz_encode.huffman_s_per_GB": _sz_s_per_gb("encode", ("repro.sz.table", "repro.sz.pack")),
    "sz_decode.huffman_s_per_GB": _sz_s_per_gb("decode", ("repro.sz.unpack",)),
}


def spans_per_request(trace: dict) -> float | None:
    """Program spans per `compress_pytree` + `decompress_pytree` pair."""
    n = len(program_spans(trace, REQUEST_SPANS[0]))
    return len(trace.get("program_spans", ())) / n if n else None


def module_seconds(trace: dict) -> list[tuple[str, float]]:
    """Device seconds per program, most first."""
    total: dict[str, float] = defaultdict(float)
    for name, _, d, _ in trace.get("device_modules", ()):
        total[name] += d
    return sorted(total.items(), key=lambda t: -t[1])


# ---------------------------------------------------------------------------
# the chip measurement
# ---------------------------------------------------------------------------


def _request(system, snap, raw: int) -> dict:
    """One request, timed and spanned as `run.run_window` does it."""
    import time

    from jax.profiler import TraceAnnotation

    clock = time.perf_counter
    with TraceAnnotation("compress"):
        t0 = clock()
        streams = system.compress(snap)
        t1 = clock()
    with TraceAnnotation("decompress"):
        t2, c2 = clock(), time.process_time()
        system.decompress(streams)
        t3, c3 = clock(), time.process_time()
    return dict(compress_s=t1 - t0, decompress_s=t3 - t2, decompress_cpu_s=c3 - c2,
                raw_bytes=raw)


def _traced_request(system, snap, raw: int, trace_dir: str) -> dict:
    """One request in a profiler trace of its own, codec proxies installed,
    with the profiler options of `bench/run.py`."""
    import jax

    from bench import proxy

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    originals = proxy.install()
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            return _request(system, snap, raw)
        finally:
            jax.profiler.stop_trace()
    finally:
        proxy.restore(originals)


def main(argv=None) -> int:
    import argparse
    import glob
    import gzip
    import json
    import shutil
    import statistics
    import tempfile

    import jax

    from bench import cells, data
    from bench.system import Program, raw_bytes

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cell = cells.load(args.workload)
    system = Program(cell.traffic["policy"])
    snap = data.snapshot(cell.shape, cell.fields, args.seed, cell.fixed_below)
    raw = raw_bytes(snap)
    system.decompress(system.compress(snap))  # warm-up request
    off, on, traced = [], [], []
    for k in range(args.requests):
        off.append(_request(system, snap, raw))
        trace_dir = tempfile.mkdtemp(prefix="bench-spans-")
        try:
            records = [_traced_request(system, snap, raw, trace_dir)]
            on += records
            path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
            trace = reduce_xplane(path)
            if args.record and k == 0:
                Path(args.record).parent.mkdir(parents=True, exist_ok=True)
                with open(path, "rb") as src, gzip.open(args.record, "wb") as dst:
                    shutil.copyfileobj(src, dst)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        readings = {name: read(trace, records) for name, read in METRICS.items()}
        readings.update({m["name"]: cell.reader(m["name"])(trace, records)
                         for m in cell.per_layer})
        traced.append({
            "metrics": readings,
            "program_idle_gaps": [list(t) for t in program_idle_gaps(trace)[:10]],
            "idle_covered_pct": idle_covered_pct(trace),
            "spans_per_request": spans_per_request(trace),
            "device_modules": [list(t) for t in module_seconds(trace)[:10]],
        })

    def rate(records, key):
        return raw * len(records) / sum(r[key] for r in records) / 1e9

    cost = {key: {"off": rate(off, f"{key}_s"), "on": rate(on, f"{key}_s")}
            for key in ("compress", "decompress")}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "device": jax.devices()[0].device_kind,
        "GBps": cost,
        "compress_s": {"off": [r["compress_s"] for r in off],
                       "on": [r["compress_s"] for r in on]},
        "median_on_over_off": statistics.median(
            b["compress_s"] / a["compress_s"] for a, b in zip(off, on)),
        "traced": traced,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
