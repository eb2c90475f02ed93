"""CPU seconds of the whole process inside `decompress_pytree` calls, per
raw GB: the decoders' work, which a host that stands still does not add to."""


def read(trace, records):
    raw_gb = sum(r["raw_bytes"] for r in records) / 1e9
    if not records or not raw_gb or any("decompress_cpu_s" not in r for r in records):
        return None
    return sum(r["decompress_cpu_s"] for r in records) / raw_gb
