"""Run one cell of `BENCHMARK.json` on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (`bench/configs/<config>.json`: an SDRBench data
set at its published shape) under a traffic mix
(`bench/traffic/<mix>.json`: a policy and the fields per snapshot). The
snapshot is generated on the device from `--seed`. One request is what an
archive writer does with it: `compress_pytree` of the snapshot, then
`decompress_pytree` of a tree rebuilt from the streams' bytes alone.

Set-up (process start to the first timed request) generates the snapshot
and runs one whole request, so every program the window uses is compiled
or read from the compile cache in the checkout. The window is a closed
loop with one writer: requests start until `--seconds` have passed, and
the one in flight finishes. Rates divide all the bytes by all the seconds
inside the calls of the window. `--trace 1` profiles the window, puts a
host span around each codec call, and prints the per-layer metrics that
`bench/metrics/<metric>.py` read from the trace.

After the window, `bench/reference.py` compares every field of every
request with the field as generated (`correct`). The last line of stdout
is the result JSON; the last lines of stderr are the compared numbers
beside their limits. Off a device listed in `bench/peaks.json`, or with
fewer devices than the cell asks for, the run exits 2 and prints no
result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from bench import cells, data, proxy, reference, tracing  # noqa: E402
from bench.system import Program, codec_split, raw_bytes, stream_bytes  # noqa: E402

#: monitoring events that mark a program traced or compiled
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileCounter:
    """Counts traces and backend compiles in this process."""

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.count += 1


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def check_devices(cell: cells.Cell, root: Path) -> list | None:
    """The cell's devices, or None (with the reason on stderr)."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if kind not in cells.peaks(root):
        print(f"bench: no peaks for device kind {kind!r} ({devs[0].platform}); "
              "this benchmark runs only on the devices in bench/peaks.json", file=sys.stderr)
        return None
    if len(devs) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX found {len(devs)}",
              file=sys.stderr)
        return None
    return devs[: cell.chips]


def run_window(system, snap: dict, seconds: float, raw: int):
    """Closed loop, one writer: (records, kept (streams, reconstruction), failed)."""
    records, kept, failed = [], [], 0
    clock = time.perf_counter
    start = clock()
    while clock() - start < seconds:
        try:
            with TraceAnnotation("compress"):
                t0 = clock()
                streams = system.compress(snap)
                t1 = clock()
            with TraceAnnotation("decompress"):
                t2, c2 = clock(), time.process_time()
                recon = system.decompress(streams)
                t3, c3 = clock(), time.process_time()
        except Exception:  # noqa: BLE001 - a failed request is counted, not fatal
            failed += 1
            traceback.print_exc()
            continue
        records.append(dict(compress_s=t1 - t0, decompress_s=t3 - t2,
                            decompress_cpu_s=c3 - c2, raw_bytes=raw,
                            stream_bytes=stream_bytes(streams), codecs=codec_split(streams)))
        kept.append((streams, recon))
    return records, kept, failed


def end_to_end(cell: cells.Cell, records: list, setup_s: float) -> dict:
    raw = sum(r["raw_bytes"] for r in records)
    every = {
        "compress_GBps": {"value": raw / sum(r["compress_s"] for r in records) / 1e9,
                          "unit": "GB/s"},
        "decompress_GBps": {"value": raw / sum(r["decompress_s"] for r in records) / 1e9,
                            "unit": "GB/s"},
        "ratio": {"value": raw / sum(r["stream_bytes"] for r in records), "unit": "x"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    return {m["name"]: every[m["name"]] for m in cell.end_to_end}


def per_layer(cell: cells.Cell, trace: dict, records: list) -> dict:
    out = {}
    for m in cell.per_layer:
        value = cell.reader(m["name"])(trace, records)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


def main(argv=None, *, root: Path = ROOT, make_system=Program, require_chip=True) -> int:
    args = parse_args(argv)
    cell = cells.load(args.workload, root)
    if require_chip:
        devs = check_devices(cell, root)
        if devs is None:
            return 2
    else:
        devs = jax.devices()[: cell.chips]
    from repro.launch.cache import cache_stats, use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    counter = CompileCounter()
    originals = proxy.install() if args.trace else None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        spec = cell.traffic["policy"]
        system = make_system(spec)
        snap = data.snapshot(cell.shape, cell.fields, args.seed, cell.fixed_below)
        raw = raw_bytes(snap)
        system.decompress(system.compress(snap))  # warm-up request
        if args.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        compiles0 = counter.count
        setup_s = time.perf_counter() - T0
        records, kept, failed = run_window(system, snap, args.seconds, raw)
        compiles = counter.count - compiles0
        if args.trace:
            jax.profiler.stop_trace()
            trace = tracing.reduce_xplane(
                glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0])
        peak = memory_peak(devs)
        originals_host = {k: np.asarray(v) for k, v in snap.items()}
        del snap
        nums = reference.numbers(originals_host, spec, kept)
    finally:
        if originals is not None:
            proxy.restore(originals)
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    attempted = len(records) + failed
    correct = bool(records) and failed == 0 and reference.within(nums)
    print(f"bench: {cell.name} seed {args.seed}: {len(records)} requests, {failed} failed, "
          f"fields per codec {records[-1]['codecs'] if records else {}}", file=sys.stderr)
    print(f"bench: compiles inside the window: {compiles}; compile cache {cache_stats()}",
          file=sys.stderr)
    d0 = devs[0]
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if args.trace:
        w = tracing.window(trace)
        device.update(busy_s=tracing.busy_seconds(trace), window_s=(w[1] - w[0]) if w else 0.0)
        result["metrics"] = per_layer(cell, trace, records)
    else:
        result["metrics"] = end_to_end(cell, records, setup_s) if records else {}
    result["device"] = device
    if args.trace:
        result["breakdown"] = {
            "device_ops": [list(t) for t in tracing.top_device_ops(trace)],
            "idle_gaps": [list(t) for t in tracing.idle_gaps(trace)[:10]],
        }
    checks = reference.check_lines(nums)
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
