"""Share of the `compress` spans in which no operation ran on the device."""

from bench import tracing


def read(trace, records):
    comp = tracing.spans(trace, "compress")
    busy = tracing.device_busy(trace)
    if not comp or not busy:
        return None
    total = sum(b - a for a, b in comp)
    per_device = [sum(tracing.overlap(iv, a, b) for a, b in comp) for iv in busy.values()]
    return 100.0 * (1.0 - sum(per_device) / len(per_device) / total)
