"""Logical-axis sharding rules (MaxText-style) + mesh context.

Model code annotates params/activations with *logical* axes; a rules table
maps them onto mesh axes per mode. Swapping rules swaps the parallelism
layout without touching model code.

Default layout (DESIGN.md §6), mesh ('pod', 'data', 'model'):
  * DP over pod x data (batch),
  * TP over model (heads / mlp / experts / vocab),
  * FSDP: weight 'embed' dims sharded over data -> 2-D weight sharding, so
    even deepseek-v2-236b fits v5e HBM (params gathered per-layer by XLA).
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

from repro.models import nn

TRAIN_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "embed": "data",      # FSDP axis for weights
    "heads": "model",
    "mlp": "model",
    "experts": "model",
    "kv": "model",
    "layers": None,
    "norm": None,
}

#: §Perf variant: weights TP-only (no FSDP gather/all-reduce over 'data' for
#: weight embed dims) — wins when params/16 fits HBM (small/medium models)
TRAIN_RULES_TP: dict[str, Any] = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "embed": None,
    "heads": "model",
    "mlp": "model",
    "experts": "model",
    "kv": "model",
    "layers": None,
    "norm": None,
}

SERVE_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "vocab": "model",
    "embed": None,        # no FSDP gather on the decode critical path
    "heads": "model",
    "mlp": "model",
    "experts": "model",
    "kv": "model",
    "layers": None,
    "norm": None,
}


def spec_for_axes(axes: tuple, rules: dict, mesh: Mesh) -> PartitionSpec:
    """Resolve logical axes -> PartitionSpec, dropping axes not in the mesh
    and never using one mesh axis twice in a single spec."""
    names = set(mesh.axis_names)
    used: set[str] = set()
    parts = []
    for ax in axes:
        rule = rules.get(ax) if ax is not None else None
        if rule is None:
            parts.append(None)
            continue
        cand = (rule,) if isinstance(rule, str) else tuple(rule)
        cand = tuple(a for a in cand if a in names and a not in used)
        if not cand:
            parts.append(None)
        else:
            used.update(cand)
            parts.append(cand[0] if len(cand) == 1 else cand)
    return PartitionSpec(*parts)


def tree_specs(axes_tree: Any, rules: dict, mesh: Mesh) -> Any:
    return jax.tree_util.tree_map(
        lambda axes: spec_for_axes(axes, rules, mesh),
        axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(a, (str, type(None))) for a in x),
    )


def tree_shardings(axes_tree: Any, rules: dict, mesh: Mesh, abstract: Any = None) -> Any:
    """NamedShardings for a logical-axes tree. With `abstract` (matching
    ShapeDtypeStruct tree), mesh axes that do not divide a dimension are
    dropped (pjit argument shardings require exact divisibility)."""
    specs = tree_specs(axes_tree, rules, mesh)
    if abstract is None:
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(mesh, spec),
            specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _fit(spec: PartitionSpec, leaf) -> NamedSharding:
        parts = []
        for i, d in enumerate(leaf.shape):
            p = spec[i] if i < len(spec) else None
            if p is None:
                parts.append(None)
                continue
            names = (p,) if isinstance(p, str) else tuple(p)
            n = int(np.prod([sizes[a] for a in names]))
            parts.append(p if d % n == 0 else None)
        return NamedSharding(mesh, PartitionSpec(*parts))

    return jax.tree_util.tree_map(
        _fit, specs, abstract, is_leaf=lambda x: isinstance(x, PartitionSpec)
    )


def cache_sharding(
    cache_desc: Any,
    mesh: Mesh,
    batch: int,
    head_sizes: set[int] = frozenset(),
    seq_shard: bool = False,
) -> Any:
    """KV/state caches: shard the batch dim over (pod, data) and any
    head-bearing dim over model, identified by size matching.

    Finds the first dim equal to `batch` (sharded DP if divisible) and the
    first later dim whose size is in `head_sizes` and divisible by the model
    axis (sharded 'model'). Leading layer-stack dims stay replicated.

    seq_shard (§Perf variant): when no head dim can take the model axis
    (n_kv_heads < model size — e.g. phi4's 8 KV heads on a 16-way model
    axis), shard the *sequence* dim of the cache over 'model' instead, so
    the KV cache never replicates (GSPMD inserts the partial-softmax
    reductions). Cuts decode HBM residency by ~model_size/1.
    """
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_n = int(np.prod([sizes[a] for a in dp])) if dp else 1
    model_n = sizes.get("model", 1)
    dp_spec = dp[0] if len(dp) == 1 else dp

    def _spec(leaf):
        parts = [None] * len(leaf.shape)
        bdim = None
        for i, s in enumerate(leaf.shape):
            if s == batch and bdim is None:
                bdim = i
                if batch % dp_n == 0:
                    parts[i] = dp_spec
                break
        if bdim is not None:
            placed = False
            for j in range(bdim + 1, len(leaf.shape)):
                if leaf.shape[j] in head_sizes and leaf.shape[j] % model_n == 0:
                    parts[j] = "model"
                    placed = True
                    break
            if not placed and seq_shard:
                for j in range(bdim + 1, len(leaf.shape)):
                    if leaf.shape[j] >= 128 * model_n and leaf.shape[j] % model_n == 0:
                        parts[j] = "model"  # sequence dim
                        break
        return PartitionSpec(*parts)

    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, _spec(s)), cache_desc)


# ---------------------------------------------------------------------------
# Shard-layout introspection (DESIGN.md §6): the plumbing the shard-local
# compression engine (core/sharded.py) and the sharded checkpoint writer use
# to reason about WHERE a jax.Array's bytes physically live without ever
# gathering them.
# ---------------------------------------------------------------------------


def mesh_of(x: Any) -> Mesh | None:
    """The concrete Mesh behind `x`'s sharding, or None for host arrays /
    single-device / non-Named shardings (callers fall back to gathering)."""
    sharding = getattr(x, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return None
    mesh = sharding.mesh
    # AbstractMesh (jax >= 0.5 tracing contexts) has no devices to address
    return mesh if hasattr(mesh, "devices") else None


def spec_entries(x: Any) -> tuple:
    """`x`'s PartitionSpec padded with None to its rank (one entry per dim)."""
    spec = tuple(x.sharding.spec)
    return spec + (None,) * (np.ndim(x) - len(spec))


def unique_shards(x: Any) -> list[tuple[tuple[int, ...], tuple[int, ...], tuple]]:
    """[(start, stop, replica_devices)] — one entry per *unique* data shard
    of `x`, in row-major shard order, with the devices holding each replica
    ordered deterministically (by device id). Start/stop are global index
    bounds per dim. This is the authoritative data-placement map the
    sharded checkpoint writer iterates: fetching `addressable_shards` for
    exactly one device per entry touches every byte exactly once."""
    imap = x.sharding.devices_indices_map(np.shape(x))
    by_slice: dict[tuple, list] = {}
    for dev, idx in imap.items():
        key = tuple(
            (0 if sl.start is None else int(sl.start),
             int(np.shape(x)[d]) if sl.stop is None else int(sl.stop))
            for d, sl in enumerate(idx)
        )
        by_slice.setdefault(key, []).append(dev)
    out = []
    for key in sorted(by_slice):
        devs = tuple(sorted(by_slice[key], key=lambda d: d.id))
        start = tuple(k[0] for k in key)
        stop = tuple(k[1] for k in key)
        out.append((start, stop, devs))
    return out


def shard_data(x: Any, device) -> np.ndarray:
    """Host copy of `x`'s local shard on `device` (no cross-device gather)."""
    for sh in x.addressable_shards:
        if sh.device == device:
            return np.asarray(sh.data)
    raise ValueError(f"device {device} holds no addressable shard of this array")


@contextlib.contextmanager
def activate(mesh: Mesh, rules: dict):
    """Bind the activation-constraint hook used by nn.shard().

    The constraints are layout hints for the partitioner, so they run on
    an Auto-typed view of `mesh`: under Explicit axes (the default of
    `jax.make_mesh`) `with_sharding_constraint` is an assert instead."""
    mesh = Mesh(
        mesh.devices, mesh.axis_names, axis_types=(AxisType.Auto,) * len(mesh.axis_names)
    )
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _divisible(dim: int, part) -> bool:
        if part is None:
            return True
        names = (part,) if isinstance(part, str) else part
        n = int(np.prod([sizes[a] for a in names]))
        return dim % n == 0

    def shard_fn(x, axes):
        if len(axes) != x.ndim:
            return x
        spec = spec_for_axes(axes, rules, mesh)
        parts = [p if _divisible(d, p) else None for d, p in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec)))]
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, PartitionSpec(*parts)))

    nn.set_shard_fn(shard_fn)
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        nn.set_shard_fn(None)
