"""The readings that the limits in `reference.LIMITS` are set from.

    python3 bench/readings.py --workload <cell> --seeds 1,2,... --control-seeds 7,8,9

In one process, on the cell's own snapshot size: for each of `--seeds`,
one request through the program (the lower readings); for each of
`--control-seeds`, one request through the control, the reference
quantizer computed in bfloat16 in the program's place (the upper
readings). Prints one JSON line per request and a summary line: the
largest program reading and the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import cells, data, reference  # noqa: E402
from bench.system import Program, codec_split, raw_bytes, stream_bytes  # noqa: E402


def reading(system, cell: cells.Cell, seed: int) -> dict:
    snap = data.snapshot(cell.shape, cell.fields, seed, cell.fixed_below)
    streams = system.compress(snap)
    recon = system.decompress(streams)
    originals = {k: np.asarray(v) for k, v in snap.items()}
    nums = reference.numbers(originals, cell.traffic["policy"], [(streams, recon)])
    ranges = {k: float(v.max()) - float(v.min()) for k, v in originals.items()}
    return dict(seed=seed, codecs=codec_split(streams), smallest_range=min(ranges.values()),
                ratio=raw_bytes(snap) / stream_bytes(streams), **nums)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    spec = cell.traffic["policy"]
    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    rows = []
    for who, seeds, system in (
        ("program", args.seeds, Program(spec)),
        ("control", args.control_seeds, reference.QuantizeReference(spec, jnp.bfloat16)),
    ):
        for s in filter(None, seeds.split(",")):
            row = dict(who=who, workload=cell.name, **reading(system, cell, int(s)))
            rows.append(row)
            print(json.dumps(row), flush=True)
    summary = {"workload": cell.name}
    for k in reference.LIMITS:
        prog = [r[k] for r in rows if r["who"] == "program"]
        ctrl = [r[k] for r in rows if r["who"] == "control"]
        summary[k] = {"program_max": max(prog, default=None),
                      "control_min": min(ctrl, default=None),
                      "limit": reference.LIMITS[k]}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
