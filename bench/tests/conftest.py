"""Bench tests run on the CPU, at tiny sizes, in a copy of the benchmark's
files with a small cell added by files alone."""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import pytest  # noqa: E402

TINY = "tiny.eb1e-4"


def add_cell(root: Path, name: str, shape, fields: int, traffic: str) -> None:
    """Add a configuration and a cell by writing files and entries only."""
    conf = json.loads((ROOT / "bench/configs/cesm-atm.json").read_text())
    conf.update(name=name, shape=list(shape))
    (root / f"bench/configs/{name}.json").write_text(json.dumps(conf))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "test", "file": f"bench/configs/{name}.json",
                             "reduced": ["shape"], "why": "test"})
    cell = f"{name}.{traffic.split('.', 1)[1]}"
    bench["workloads"].append({"name": cell, "config": name, "traffic": traffic,
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        m.setdefault("workloads", []).append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture
def tiny_root(tmp_path) -> Path:
    """A copy of BENCHMARK.json and bench/ with the cell `tiny.eb1e-4`:
    eight 64x96 fields under the `dump8.eb1e-4` traffic."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    add_cell(tmp_path, "tiny", (64, 96), 8, "dump8.eb1e-4")
    return tmp_path


def run_cell(root: Path, capsys, *argv, **kw) -> tuple[int, dict | None]:
    """`bench.run.main` on the CPU; (exit code, result line or None)."""
    from bench import run

    rc = run.main(list(argv), root=root, require_chip=False, **kw)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
