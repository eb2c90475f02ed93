"""Mesh builders. Functions (not module constants) so importing never
touches jax device state.

Every mesh has Auto axes: the model's sharding annotations are layout
constraints for the partitioner to propagate (`jax.make_mesh` defaults to
Explicit axes, under which `with_sharding_constraint` is an assert)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16) ('data','model') single pod (256 chips); (2,16,16)
    ('pod','data','model') for 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh():
    """Single-device mesh for smoke/integration tests."""
    return _mesh((1, 1), ("data", "model"))


def make_device_mesh():
    """('data', 'model') mesh over the devices that are present, data
    parallel: (n, 1) — what the train and serve launchers run under."""
    return _mesh((len(jax.devices()), 1), ("data", "model"))


def make_emulated_mesh(shape=(2, 4), axes=("data", "model")):
    """Mesh over emulated CPU devices (DESIGN.md §6 test harness).

    Requires `XLA_FLAGS=--xla_force_host_platform_device_count=N` to be in
    the environment BEFORE jax initializes — tests get this from
    `tests/conftest.py`'s early-import hook; scripts (benchmarks, the
    sharded-checkpoint dryrun) set it at the top of their own module,
    before importing jax. In a multi-PROCESS job
    (`repro.runtime.dist.initialize` — workers spawned by
    `launch/mhrun.py`), `jax.device_count()` is already GLOBAL, so the
    same call builds the same mesh over all hosts' devices: 8 global
    devices give an identical (2, 4) layout at 1, 2, or 4 processes,
    which is what makes cross-host-count decision parity testable."""
    n = int(jax.device_count())
    need = 1
    for s in shape:
        need *= int(s)
    if n < need:
        raise RuntimeError(
            f"make_emulated_mesh{tuple(shape)} needs {need} devices, have {n}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax "
            "initializes (per process under launch/mhrun.py)"
        )
    return _mesh(shape, axes)


def describe_mesh(mesh) -> dict:
    """Loggable mesh summary including the per-process device split —
    the multi-host dryrun and test workers record it so a mis-assembled
    job (wrong device counts per host) is visible in the artifacts."""
    per_process: dict[int, int] = {}
    for d in mesh.devices.flat:
        p = int(getattr(d, "process_index", 0))
        per_process[p] = per_process.get(p, 0) + 1
    return dict(
        shape=dict(zip(mesh.axis_names, (int(s) for s in mesh.devices.shape))),
        devices=int(mesh.devices.size),
        process_index=int(jax.process_index()),
        process_count=int(jax.process_count()),
        devices_per_process={str(k): v for k, v in sorted(per_process.items())},
    )
