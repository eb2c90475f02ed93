"""Multi-host save-parity smoke for the CI bench gate (DESIGN.md §6.2).

Saves the same synthetic mixed-policy state cooperatively at each host
count in `processes` — real `jax.distributed` jobs spawned through
`repro.launch.mhrun`, 8 global emulated devices split across them — and
differences the results: Stage I/II decisions, error bounds, segment
geometry, and decompressed bytes must be bit-identical across host
counts (the psum-reconciliation contract the multihost test suite proves
exhaustively; this smoke keeps the invariant wired into the bench gate's
`multihost_save_parity` absolute check, which flips EMPTY across host
counts or fails).

The worker program is `tests/multihost/worker.py` (scenario ``save``) —
one definition of the differential state for the suite and the gate, so
the two can never drift apart.

    PYTHONPATH=src python -m benchmarks.bench_multihost
"""

from __future__ import annotations

import os
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
WORKER = ROOT / "tests" / "multihost" / "worker.py"


def run(processes: tuple[int, ...] = (1, 2), fields: int = 3, dim: int = 128) -> dict:
    """-> {hosts, flips, value_mismatches, wall_seconds} across `processes`."""
    from repro.launch import mhrun

    env = {
        "PYTHONPATH": os.pathsep.join(
            [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]
        ).rstrip(os.pathsep)
    }
    payloads: dict[int, list[dict]] = {}
    wall: dict[str, float] = {}
    with tempfile.TemporaryDirectory() as wd:
        for nproc in processes:
            t0 = time.perf_counter()
            results = mhrun.run(
                [sys.executable, str(WORKER)],
                nproc,
                scenario="save",
                args=dict(
                    directory=os.path.join(wd, f"ckpt{nproc}p"),
                    fields=fields, dim=dim,
                ),
                local_devices=8 // nproc,
                timeout_s=600.0,
                workdir=os.path.join(wd, f"mhrun{nproc}p"),
                extra_env=env,
            )
            payloads[nproc] = mhrun.require_success(results)
            wall[f"{nproc}p"] = time.perf_counter() - t0

    base = payloads[processes[0]][0]
    flips: list[str] = []
    mismatches: list[str] = []
    for nproc in processes:
        for p in payloads[nproc]:
            for name, bits in base["summary"]["selection_bits"].items():
                if p["summary"]["selection_bits"].get(name) != bits:
                    flips.append(f"{nproc}p:{name}")
            if p["summary"] != base["summary"]:
                flips.append(f"{nproc}p:<manifest>")
            for name, digest in base["hashes"].items():
                if p["hashes"].get(name) != digest:
                    mismatches.append(f"{nproc}p:{name}")
    return dict(
        hosts=[int(p) for p in processes],
        flips=sorted(set(flips)),
        value_mismatches=sorted(set(mismatches)),
        wall_seconds=wall,
    )


def main() -> int:
    out = run()
    for k, v in out.items():
        print(f"{k}: {v}")
    bad = out["flips"] + out["value_mismatches"]
    print("PASS" if not bad else f"FAIL: {bad[:8]}")
    return 0 if not bad else 1


if __name__ == "__main__":
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    sys.exit(main())
