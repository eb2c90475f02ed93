"""Share of each `compress` span before its first `host_encode.*` span:
copying the leaves to the host, grouping by policy and `select_many`."""

from bench import tracing


def read(trace, records):
    comp = tracing.spans(trace, "compress")
    enc = tracing.spans_with_prefix(trace, "host_encode.")
    if not comp:
        return None
    before = 0.0
    for a, b in comp:
        starts = [s for _, s, _ in enc if a <= s <= b]
        before += (min(starts) if starts else b) - a
    return 100.0 * before / sum(b - a for a, b in comp)
