"""Seconds in `host_decode.*` spans, summed over all threads, per raw GB."""

from bench import tracing


def read(trace, records):
    spans = tracing.spans_with_prefix(trace, "host_decode.")
    raw_gb = sum(r["raw_bytes"] for r in records) / 1e9
    if not spans or not raw_gb:
        return None
    return sum(e - s for _, s, e in spans) / raw_gb
