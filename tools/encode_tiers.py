"""Stage III on the host coders against the device tier, per field size.

    python3 tools/encode_tiers.py [--log2 16 18 20 22 24] [--reps 2] [--seed 0]

For each size 2^k, a 3-D float32 field with a k^-3.67 power spectrum (the
generator of `bench/data.py`) is selected at `fixed_accuracy(eb_rel=1e-4)`.
Then each lossy codec encodes it with its host coder (`encode`) and its
device encoder (`encode_device`, DESIGN.md §3.7): once cold, then `--reps`
warm repeats. One JSON line per (size, codec) gives the seconds of the
first call (compile included) and of the fastest warm repeat of each tier.
`selector.DEVICE_ENCODE_MIN_VALUES` is set from this table (PERF.md §7).
Off a TPU it exits 2: a CPU timing says nothing about the chip.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

#: one 3-D shape per log2 of the value count
SHAPES = {16: (16, 64, 64), 17: (32, 64, 64), 18: (64, 64, 64), 19: (32, 128, 128),
          20: (64, 128, 128), 21: (128, 128, 128), 22: (64, 256, 256),
          23: (128, 256, 256), 24: (256, 256, 256)}


def timed(fn, reps: int) -> tuple[float, float, object]:
    """(first call, fastest of `reps` more, in seconds; the first result)."""
    t0 = time.perf_counter()
    out = fn()
    first = time.perf_counter() - t0
    warm = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        warm.append(time.perf_counter() - t0)
    return first, min(warm), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--log2", type=int, nargs="+", default=[16, 18, 20, 22, 24],
                    choices=sorted(SHAPES))
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    import jax
    import numpy as np

    from bench import data
    from repro.core import codecs, selector

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"encode_tiers: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    for k in args.log2:
        shape = SHAPES[k]
        x = np.asarray(data.spectral_field(shape, -3.6666666666666665,
                                           data.seed_key(args.seed + k)))
        sel = selector.select(x, eb_rel=1e-4)
        for name in ("sz", "zfp"):
            codec = codecs.get(name)
            host = timed(lambda: codec.encode(x, sel), args.reps)
            device = timed(lambda: codec.encode_device(x, sel), args.reps)
            print(json.dumps({
                "log2": k, "shape": shape, "codec": name, "selected": sel.codec,
                "host_first_s": host[0], "host_s": host[1],
                "device_first_s": device[0], "device_s": device[1],
                "declined": device[2] is None,
                "device_kind": dev.device_kind,
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
