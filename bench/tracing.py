"""From a profiler trace to the intervals the per-layer readers use.

`reduce_xplane` keeps two kinds of interval from the `.xplane.pb` that
`jax.profiler.trace` writes:

* device operations: the events of each device plane's op line, which
  `jax.profiler.ProfileData` puts on the host's clock;
* host spans: the benchmark's own `TraceAnnotation`s ("compress",
  "decompress", and the codec proxies' "host_encode.<codec>" and
  "host_decode.<codec>"), with the thread that ran each.

The reduced trace is a plain dict of lists, in seconds, so that readers
and tests need no profiler: {"device_ops": [[name, start, dur, device]],
"spans": [[name, start, dur, thread]]}.
"""

from __future__ import annotations

import re
from collections import defaultdict

#: host spans the benchmark writes; the codec proxies' spans go by prefix
SPANS = ("compress", "decompress")
SPAN_PREFIXES = ("host_encode.", "host_decode.")
#: the device plane's line of individual operations
OP_LINE = "XLA Ops"
#: an HLO instruction as the TPU trace names its ops: "%name = type[dims]{layout} op(...)"
_HLO = re.compile(r"^(%[\w.\-]+) = (\w+\[[\d,]*\])\S* ([\w\-]+)\(")


def _is_span(name: str) -> bool:
    return name in SPANS or name.startswith(SPAN_PREFIXES)


def op_name(name: str) -> str:
    """"%fusion.3 fusion f32[450,4]" for an HLO instruction, else the name."""
    m = _HLO.match(name)
    if m:
        return f"{m[1]} {m[3]} {m[2]}"
    return name.split(" = ", 1)[0]


def reduce_xplane(path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name != OP_LINE:
                    continue
                for e in line.events:
                    ops.append([op_name(e.name), e.start_ns * 1e-9, e.duration_ns * 1e-9, plane.name])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if _is_span(e.name):
                        spans.append([e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                                      line.name])
    return {"device_ops": ops, "spans": spans}


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint (start, end) covering the given intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def overlap(disjoint, a: float, b: float) -> float:
    """Length of [a, b] covered by sorted disjoint intervals."""
    return sum(max(0.0, min(b, y) - max(a, x)) for x, y in disjoint if x < b and y > a)


def spans(trace: dict, name: str) -> list[tuple[float, float]]:
    return sorted((s, s + d) for n, s, d, _ in trace["spans"] if n == name)


def spans_with_prefix(trace: dict, prefix: str) -> list[tuple[str, float, float]]:
    return sorted(((n, s, s + d) for n, s, d, _ in trace["spans"] if n.startswith(prefix)),
                  key=lambda t: t[1])


def device_busy(trace: dict) -> dict[str, list[tuple[float, float]]]:
    """Per device, the union of its operations' intervals."""
    per: dict[str, list] = defaultdict(list)
    for _, s, d, dev in trace["device_ops"]:
        per[dev].append((s, s + d))
    return {dev: union(iv) for dev, iv in per.items()}


def window(trace: dict) -> tuple[float, float] | None:
    """From the first request's start to the last one's end."""
    req = spans(trace, "compress") + spans(trace, "decompress")
    if not req:
        return None
    return min(a for a, _ in req), max(b for _, b in req)


def busy_seconds(trace: dict) -> float:
    """Device-busy seconds inside the window, averaged over the devices."""
    w = window(trace)
    busy = device_busy(trace)
    if w is None or not busy:
        return 0.0
    return sum(overlap(iv, *w) for iv in busy.values()) / len(busy)


def complement(disjoint, a: float, b: float) -> list[tuple[float, float]]:
    """The parts of [a, b] that sorted disjoint intervals leave uncovered."""
    out, cur = [], a
    for x, y in disjoint:
        if y <= cur:
            continue
        if x >= b:
            break
        if x > cur:
            out.append((cur, x))
        cur = max(cur, y)
    if cur < b:
        out.append((cur, b))
    return out


def idle_gaps(trace: dict, within: str = "compress",
              shortest: float = 1e-6) -> list[tuple[str, float]]:
    """Device-idle stretches of `shortest` seconds or more inside the
    `within` spans, each named for the codec span that covers most of it
    (`within` where none does), longest first. Shorter ones are the
    rounding between back-to-back device ops."""
    busy = device_busy(trace)
    dev_union = union(iv for ivs in busy.values() for iv in ivs)
    inner = spans_with_prefix(trace, SPAN_PREFIXES[0]) + spans_with_prefix(
        trace, SPAN_PREFIXES[1])
    gaps = []
    for a, b in spans(trace, within):
        for g0, g1 in complement(dev_union, a, b):
            if g1 - g0 < shortest:
                continue
            label, best = within, 0.0
            for n, s, e in inner:
                cov = min(g1, e) - max(g0, s)
                if cov > best:
                    label, best = n, cov
            gaps.append((label, g1 - g0))
    return sorted(gaps, key=lambda t: -t[1])


def top_device_ops(trace: dict, n: int = 10) -> list[tuple[str, float]]:
    total: dict[str, float] = defaultdict(float)
    for name, _, d, _ in trace["device_ops"]:
        total[name] += d
    return sorted(total.items(), key=lambda t: -t[1])[:n]
