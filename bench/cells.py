"""Find a cell's configuration, traffic mix and metric readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own under `bench/`; this module
only looks them up, so a cell is added by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parents[1]


@dataclass(frozen=True)
class Field:
    """One field's generation rule: power-law slope, nonlinearity, noise."""

    name: str
    slope: float
    nonlin: str | None
    noise: float


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[dict, ...]
    per_layer: tuple[dict, ...]
    root: Path

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.config["shape"])

    @property
    def fields(self) -> tuple[Field, ...]:
        """The snapshot's fields: the first `fields` of the configuration."""
        n, rules = int(self.traffic["fields"]), self.config["fields"]
        if n > len(rules):
            raise ValueError(f"{self.name}: traffic asks for {n} fields, the "
                             f"configuration holds {len(rules)}")
        return tuple(Field(f["name"], float(f["slope"]), f.get("nonlin"),
                           float(f.get("noise", 0.0))) for f in rules[:n])

    @property
    def fixed_below(self) -> float:
        """Modes below this many cycles per cell share their phases over seeds."""
        return float(self.config.get("fixed_below", 0.0))

    def reader(self, metric: str) -> Callable:
        """`read(trace, records)` of `metrics/<metric>.py`."""
        path = self.root / "bench" / "metrics" / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(m: dict) -> bool:
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if applies(m)),
        per_layer=tuple(m for m in bench["per_layer"] if applies(m)),
        root=root,
    )


def peaks(root: Path = ROOT) -> dict:
    """Published peaks keyed by JAX's `device_kind`."""
    return json.loads((root / "bench" / "peaks.json").read_text())["devices"]
