"""Shard-local selection engine — distributed Algorithm 1 (DESIGN.md §6).

The paper's headline evaluation is *parallel*: 1,024 ranks, each
compressing its own fields. This module closes the reproduction's gap to
that setting: Stage I/II of Algorithm 1 runs under `shard_map` over the
training mesh, so every device computes estimator statistics on its LOCAL
shard and the per-field decision is reconciled with a cheap collective of
the §4–§5 sufficient statistics — no full-tensor gather ever happens on
the selection path, and the byte encoders then run per shard (each host
compresses only the bytes it already holds). The collectives make this
multi-HOST for free (DESIGN.md §6.2): under `jax.process_count() > 1`
the merged statistics — and hence every decision and bound — are
identical on all processes, and `encode_plan(..., host=)` filters the
segment list to the ones a given process owns, which is what the
checkpoint writer's per-host segment files build on.

Two reconciliation strategies, both exposed through `plan_tree`:

* ``stats`` (fixed_accuracy default) — each shard computes its owned
  sample blocks' sufficient statistics in-graph: value range via a global
  min/max, exact ZFP coder bits per block (integer), EC-point truncation
  error energy, and the SZ integer-Lorenzo residual *bin counts* at the
  iso-PSNR bin size. A `psum` over the mesh merges them exactly (integer
  sums and min/max are reduction-order-free), and the decision formulas of
  §4–§5 run on the merged statistics — the same expressions the unsharded
  batched path evaluates, so decisions agree to estimator ulps and the
  derived SZ bound is bit-identical thanks to the `PSNR_MATCH_QUANTUM`
  snap (DESIGN.md §1).
* ``samples`` (target modes, and an exact-parity option for
  fixed_accuracy) — each shard extracts its owned sample *blocks*
  (`r_sp` ≈ 5% of the bytes) with a one-plane `ppermute` halo exchange,
  they are all-gathered in global block order, and the existing batched
  deciders (`selector._run_select_batches`, the §7 controller) run on
  them. Because the gathered blocks are bit-identical to what
  `estimator.gather_blocks_np` would produce from the unsharded tensor,
  the decisions are bit-identical to the unsharded path by construction.

Block ownership: the global 4^n sample lattice (`estimator.block_starts`
on the *folded global view*) is partitioned on host from the sharding's
`devices_indices_map`; a block belongs to the shard containing it, and
within a replica group blocks round-robin across the replicas so even
fully-replicated fields parallelize. Eligibility requires every sharded
view dim to split evenly into 4-aligned shards (one mesh axis per dim);
anything else — uneven shards, sharded middle dims of a >3-D fold,
non-Named shardings — falls back to the gather path per field, which is
exactly the unsharded engine, so correctness never depends on layout.

The halo exchange: SZ's Lorenzo residuals predict each sample block from
its ORIGINAL neighbors (zero outside the domain). A shard's leading block
along a sharded dim needs the previous shard's trailing element plane, so
the body prepends one `ppermute`d plane per sharded dim (zeros arrive at
the global boundary, matching the convention); corner halos compose
because each exchange forwards the already-extended array.
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from functools import lru_cache
from types import SimpleNamespace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.runtime import dist
from repro.runtime import sharding as rsh

from . import codecs as _codecs
from . import controller as ctl
from . import estimator as est
from . import quality as qual
from . import selector as select_mod
from .embedded import exact_coder_bits_blocks, plane_step
from .policy import TARGET_FIELD, Policy, policy_from_kwargs
from .selector import (
    Selection,
    _degenerate_selection,
    _fold_ndim,
    _max_batch_blocks,
    _next_pow2,
    _pick_codec,
    _run_select_batches,
)
from .transforms import block_transform_nd, bot_linf_gain, bot_matrix


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


# ---------------------------------------------------------------------------
# Layout analysis: can this array's sharding carry the engine?
# ---------------------------------------------------------------------------


def fold_plan(shape: tuple[int, ...]) -> tuple[tuple[int, ...], list[tuple[int, ...]]]:
    """(view_shape, groups): the `selector._fold_ndim` fold expressed as a
    plan — groups[i] lists the ORIGINAL dims merged into view dim i.

    Genuinely-3-D fields (Hurricane/NYX volumes) keep all three dims:
    ranks above 3 fold leading axes into view dim 0 but never below 3-D,
    so the folded view stays eligible for the 3-D kernel tier
    (DESIGN.md §3.4–§3.5) and for 3-D shard-local selection. Only a
    leading dim too short for a 4-wide block (< 4) is merged away."""
    dims = list(shape)
    groups: list[tuple[int, ...]] = [(d,) for d in range(len(dims))]
    if len(dims) > 3:
        lead = tuple(range(len(dims) - 2))
        groups = [lead, (len(dims) - 2,), (len(dims) - 1,)]
        dims = [int(np.prod(shape[:-2]))] + list(shape[-2:])
    size = int(np.prod(shape)) if shape else 0
    while len(dims) > 1 and dims[0] < 4 and size:
        groups = [groups[0] + groups[1]] + groups[2:]
        dims = [dims[0] * dims[1]] + dims[2:]
    return tuple(dims), groups


@dataclass(frozen=True)
class ShardSeg:
    """One unique data shard of a field's folded view (replicas share it)."""

    start: tuple[int, ...]  # view coords
    stop: tuple[int, ...]
    devices: tuple[Any, ...]  # replica group, deterministic (device-id) order


@dataclass(frozen=True)
class FieldLayout:
    """How a field's folded global view maps onto mesh shards."""

    mesh: Mesh
    view_shape: tuple[int, ...]
    local_view: tuple[int, ...]  # uniform shard extent, view coords
    axis_of_dim: tuple[str | None, ...]  # mesh axis partitioning each view dim
    orig_spec: tuple  # PartitionSpec entries over the ORIGINAL dims
    segs: tuple[ShardSeg, ...]


def analyze(x: Any) -> FieldLayout | None:
    """The engine-eligible layout of `x`, or None (gather fallback).

    Eligible: NamedSharding on a concrete mesh; each sharded dim carried
    by exactly one mesh axis; folding merges only unsharded dims (except
    the leading one); every sharded view dim splits evenly into shards
    that are multiples of the 4-wide block. The returned `local_view` is
    identical on every device — a `shard_map` requirement."""
    mesh = rsh.mesh_of(x)
    if mesh is None or np.ndim(x) == 0:
        return None
    shape = tuple(int(s) for s in np.shape(x))
    spec = rsh.spec_entries(x)
    view_shape, fold_groups = fold_plan(shape)
    axis_of_dim: list[str | None] = []
    for vdim, group in enumerate(fold_groups):
        sharded = [d for d in group if spec[d] is not None]
        if not sharded:
            axis_of_dim.append(None)
            continue
        if sharded != [group[0]]:
            return None  # a merged inner dim is sharded: slices interleave
        entry = spec[group[0]]
        if not isinstance(entry, str):
            return None  # one dim over several mesh axes: keep it simple
        n = int(mesh.shape[entry])
        if n > 1:
            if shape[group[0]] % n:
                return None  # uneven shards break shard_map uniformity
            local = view_shape[vdim] // n
            if local % 4 or local < 4:
                return None  # shard boundary would split a 4-block
        axis_of_dim.append(entry if n > 1 else None)
    local_view = tuple(
        v // (mesh.shape[a] if a else 1) for v, a in zip(view_shape, axis_of_dim)
    )
    inner = {g[0]: int(np.prod([shape[d] for d in g[1:]], initial=1)) for g in fold_groups}
    lead = {g[0]: vd for vd, g in enumerate(fold_groups)}
    segs = []
    for start_o, stop_o, devs in rsh.unique_shards(x):
        start_v = [0] * len(view_shape)
        stop_v = list(view_shape)
        for d, vd in lead.items():
            start_v[vd] = start_o[d] * inner[d]
            stop_v[vd] = start_v[vd] + (stop_o[d] - start_o[d]) * inner[d]
        segs.append(ShardSeg(tuple(start_v), tuple(stop_v), devs))
    return FieldLayout(
        mesh, view_shape, local_view, tuple(axis_of_dim), tuple(spec), tuple(segs)
    )


# ---------------------------------------------------------------------------
# Block ownership: partition the global sample lattice across shards
# ---------------------------------------------------------------------------


def _owned_starts(
    layout: FieldLayout, starts: np.ndarray
) -> dict[Any, tuple[list[tuple[int, ...]], list[int]]]:
    """device -> (local extended-array block starts, global slot indices).

    A block belongs to the shard containing it (shard boundaries are
    4-aligned, so containment is total); within a replica group, blocks
    round-robin across the devices so replicated fields still spread the
    estimator work. Local starts index the halo-extended local array: the
    prepended plane shifts everything by +1, so the 5-wide halo window of
    global block `g` starts at `g - seg.start` exactly."""
    nd = len(layout.view_shape)
    segmap = {s.start: s for s in layout.segs}
    rr: dict[tuple, int] = {s.start: 0 for s in layout.segs}
    owned: dict[Any, tuple[list, list]] = {
        d: ([], []) for s in layout.segs for d in s.devices
    }
    for slot, g in enumerate(np.asarray(starts, np.int64)):
        key = tuple(
            (int(g[d]) // layout.local_view[d]) * layout.local_view[d]
            if layout.axis_of_dim[d]
            else 0
            for d in range(nd)
        )
        seg = segmap[key]
        j = rr[key]
        rr[key] = j + 1
        dev = seg.devices[j % len(seg.devices)]
        owned[dev][0].append(tuple(int(g[d]) - key[d] for d in range(nd)))
        owned[dev][1].append(slot)
    return owned


@lru_cache(maxsize=256)
def _starts_plan(layout: FieldLayout, starts_bytes: bytes, n_blocks: int):
    """Cached (owned-starts map, padded per-device count, stacked device
    array) for one (layout, sample grid): the partition is deterministic,
    and an in-situ loop re-saves the same shapes every checkpoint — this
    keeps the per-save host work at dict lookups instead of a fresh
    ownership sweep + device_put per field."""
    nd = len(layout.view_shape)
    starts = np.frombuffer(starts_bytes, np.int64).reshape(n_blocks, nd)
    owned = _owned_starts(layout, starts)
    mx = _next_pow2(max([len(v[1]) for v in owned.values()] + [1]))
    stacked = _stacked_starts(layout.mesh, owned, nd, mx)
    return owned, mx, stacked


def _stacked_starts(mesh: Mesh, per_dev: dict, nd: int, mx: int) -> jax.Array:
    """(n_devices, mx, nd+1) int32 — per-device [local starts | slot], padded
    with slot = -1, placed so shard_map hands each device its own row. The
    ownership map covers GLOBAL devices, so on a multi-process mesh the
    array is assembled via `make_array_from_callback` (each process
    contributes only its addressable rows — `device_put` cannot reach a
    remote device)."""
    n = int(mesh.devices.size)
    ns = NamedSharding(mesh, PartitionSpec(tuple(mesh.axis_names)))
    arr = np.zeros((n, mx, nd + 1), np.int32)
    arr[:, :, nd] = -1
    imap = ns.devices_indices_map((n, mx, nd + 1))
    for dev, idx in imap.items():
        row = 0 if idx[0].start is None else int(idx[0].start)
        lsts, slots = per_dev.get(dev, ([], []))
        for k, (lst, slot) in enumerate(zip(lsts, slots)):
            arr[row, k, :nd] = lst
            arr[row, k, nd] = slot
    return dist.put_global(arr, ns)


# ---------------------------------------------------------------------------
# The shard_map bodies
# ---------------------------------------------------------------------------


_F32 = jnp.float32


def _halo_extend(v: jax.Array, axis_of_dim: tuple, mesh: Mesh) -> jax.Array:
    """Prepend one halo plane per view dim: the previous shard's trailing
    plane along sharded dims (`ppermute`; index-0 shards receive zeros —
    the global-boundary convention), zeros along unsharded dims. Done dim
    by dim so corner halos compose through the already-extended planes."""
    for dim, ax in enumerate(axis_of_dim):
        if ax is not None and int(mesh.shape[ax]) > 1:
            n = int(mesh.shape[ax])
            plane = jax.lax.slice_in_dim(v, v.shape[dim] - 1, v.shape[dim], axis=dim)
            recv = jax.lax.ppermute(plane, ax, [(i, i + 1) for i in range(n - 1)])
            v = jnp.concatenate([recv, v], axis=dim)
        else:
            pad = jnp.zeros(v.shape[:dim] + (1,) + v.shape[dim + 1 :], v.dtype)
            v = jnp.concatenate([pad, v], axis=dim)
    return v


def _gather_ext(ext: jax.Array, lst: jax.Array, nd: int) -> jax.Array:
    """(mx, 5, ..) halo blocks of the extended local array at `lst` starts
    (traced values — unlike `estimator.gather_blocks`' static grid). Pad
    rows gather in-bounds garbage that callers mask / drop by slot."""
    mx = lst.shape[0]
    offs = jnp.arange(5)
    bidx = []
    for d in range(nd):
        i = jnp.clip(lst[:, d][:, None] + offs[None, :], 0, ext.shape[d] - 1)
        sh = [mx] + [1] * nd
        sh[1 + d] = 5
        bidx.append(i.reshape(sh))
    return ext[tuple(bidx)]


@dataclass(frozen=True)
class _FieldDesc:
    """Static per-field signature of one engine launch (the jit cache key)."""

    orig_local: tuple[int, ...]  # local shard shape, original dims
    orig_spec: tuple
    view_shape: tuple[int, ...]
    local_view: tuple[int, ...]
    axis_of_dim: tuple
    mx: int  # padded per-device block count


def _field_stats(halo, valid, eb, vr, size_f, nd, transform, all_axes):
    """One field's §4–§5 sufficient statistics from its owned halo blocks,
    psum-merged over the mesh, reduced to (br_sz, br_zfp, psnr_zfp, eb_sz)
    with exactly the formulas of `estimator.estimate_zfp_many` /
    `estimate_sz` — integer statistics (coder bits, bin counts, escape
    counts) merge exactly; the only floating sums (EC error energy) feed
    the PSNR whose `PSNR_MATCH_QUANTUM` snap absorbs reduction-order ulps
    before the SZ bound is derived (DESIGN.md §1, §6)."""
    bsz = 4**nd

    def psum(v):
        return jax.lax.psum(v, all_axes)

    nohalo = halo[(slice(None),) + (slice(1, None),) * nd]
    # --- ZFP at eb: exact coder bits (int) + EC truncation error (§5) ---
    n_s = nohalo.shape[0]
    mxab = jnp.maximum(jnp.max(jnp.abs(nohalo.reshape(n_s, -1)), axis=1), 1e-30)
    e = jnp.ceil(jnp.log2(mxab)).astype(jnp.int32)
    norm = nohalo * jnp.exp2(-e.astype(_F32)).reshape((-1,) + (1,) * nd)
    T = jnp.asarray(bot_matrix(transform), _F32)
    coeffs = block_transform_nd(norm, T, nd)
    gain_n = bot_linf_gain(transform) ** nd
    step = plane_step(eb, e, gain_n)
    bits_blk = exact_coder_bits_blocks(coeffs, step)  # integer-valued f32
    bits = psum(jnp.sum(jnp.where(valid, bits_blk, 0.0).astype(jnp.int32)))
    sel_pts = np.flatnonzero(est._ec_point_mask(nd).reshape(-1))
    s_ = step.reshape(-1, 1).astype(_F32)
    co = coeffs.reshape(n_s, -1)[:, sel_pts]
    mt = jnp.trunc(jnp.abs(co) / s_)
    rec = jnp.sign(co) * jnp.where(mt > 0, (mt + 0.5) * s_, 0.0)
    scale = jnp.exp2(e.astype(_F32)).reshape(-1, 1)
    vr32 = jnp.maximum(vr, 1e-30)
    err2n_blk = jnp.sum(jnp.square((co - rec) * scale), axis=1) / jnp.square(vr32)
    err2 = psum(jnp.sum(jnp.where(valid, err2n_blk, 0.0)))
    nblk = psum(jnp.sum(valid.astype(jnp.int32))).astype(_F32)
    br_zfp = bits.astype(_F32) / jnp.maximum(nblk * bsz, 1.0)
    mse_over_vr2 = err2 / jnp.maximum(nblk * len(sel_pts), 1.0)
    psnr = -10.0 * jnp.log10(jnp.maximum(mse_over_vr2, 1e-60))
    # --- iso-PSNR match -> SZ bin size (§1), then SZ bin counts (§4) ---
    delta = est.sz_delta_for_psnr(psnr, vr)
    eb_sz = jnp.clip(delta / 2.0, eb * 1e-6, eb)
    dlt = 2.0 * eb_sz
    d = jnp.round(halo / dlt)
    for ax in range(1, nd + 1):
        d = jax.lax.slice_in_dim(d, 1, d.shape[ax], axis=ax) - jax.lax.slice_in_dim(
            d, 0, d.shape[ax] - 1, axis=ax
        )
    k_raw = d.reshape(-1)
    valid_s = jnp.repeat(valid, bsz)
    half = (est.PDF_BINS - 1) // 2
    esc = psum(jnp.sum((valid_s & (jnp.abs(k_raw) > half)).astype(jnp.int32)))
    k = (jnp.clip(k_raw, -half, half) + half).astype(jnp.int32)
    hist = (
        jnp.zeros((est.PDF_BINS,), jnp.int32)
        .at[jnp.where(valid_s, k, 0)]
        .add(valid_s.astype(jnp.int32))
    )
    hist = psum(hist)  # the merged bin counts ARE the §4 sufficient statistic
    ofrac = esc.astype(_F32) / jnp.maximum(jnp.sum(hist), 1).astype(_F32)
    br_sz = est.sz_bitrate_from_hist(hist, ofrac, size_f)
    return br_sz, br_zfp, psnr, eb_sz


@lru_cache(maxsize=32)
def _engine_fn(
    mesh: Mesh,
    descs: tuple[_FieldDesc, ...],
    kind: str,
    transform: str,
    replicate_out: bool = False,
):
    """Jitted shard_map over one batch of engine-eligible fields.

    kind='samples': each device extracts its owned halo blocks; outputs
    (blocks, slots) stacked over devices for host reassembly into global
    block order. kind='stats': the full §4–§5 statistic computation +
    psum reconciliation runs in-graph; outputs per-field decision scalars.
    Cached per (mesh, field signatures, kind) — the checkpoint loop hits
    the same signature every step.

    `replicate_out` (multi-process meshes, samples mode): the host cannot
    `device_get` a cross-process-sharded output, so the blocks/slots are
    `all_gather`ed IN-GRAPH over every mesh axis and come back replicated
    (out_specs `P()`). The gather order differs from shard_map's stacking,
    but reassembly scatters by slot index, so the result is identical —
    every process sees the full global block set and the downstream
    deciders run on bit-identical inputs on every host."""
    names = tuple(mesh.axis_names)

    def body(xs, sts, eb_f, vr_f, size_f):
        blocks_out, slots_out, stats_out = [], [], []
        for i, (x_loc, st, dsc) in enumerate(zip(xs, sts, descs)):
            nd = len(dsc.view_shape)
            v = x_loc.reshape(dsc.local_view).astype(_F32)
            ext = _halo_extend(v, dsc.axis_of_dim, mesh)
            st = st[0]  # (1, mx, nd+1) -> (mx, nd+1)
            lst, slot = st[:, :nd], st[:, nd]
            halo = _gather_ext(ext, lst, nd)
            if kind == "samples":
                if replicate_out:
                    halo = jax.lax.all_gather(halo, names, axis=0, tiled=True)
                    slot = jax.lax.all_gather(slot, names, axis=0, tiled=True)
                blocks_out.append(halo)
                slots_out.append(slot)
            else:
                stats_out.append(
                    _field_stats(
                        halo, slot >= 0, eb_f[i], vr_f[i], size_f[i], nd, transform, names
                    )
                )
        if kind == "samples":
            return tuple(blocks_out), tuple(slots_out)
        return tuple(stats_out)

    in_specs = (
        tuple(PartitionSpec(*d.orig_spec) for d in descs),
        tuple(PartitionSpec(names) for _ in descs),
        PartitionSpec(),
        PartitionSpec(),
        PartitionSpec(),
    )
    if kind == "samples" and replicate_out:
        out_specs = (
            tuple(PartitionSpec() for _ in descs),
            tuple(PartitionSpec() for _ in descs),
        )
    elif kind == "samples":
        out_specs = (
            tuple(
                PartitionSpec(names, *([None] * len(d.view_shape))) for d in descs
            ),
            tuple(PartitionSpec(names) for _ in descs),
        )
    else:
        out_specs = tuple(
            (PartitionSpec(), PartitionSpec(), PartitionSpec(), PartitionSpec())
            for _ in descs
        )
    return jax.jit(_smap(body, mesh, in_specs, out_specs))


@jax.jit
def _minmax_jit(xs):
    """Per-field global (min, max) of the f32 view — XLA partitions the
    reduction shard-locally and all-reduces the scalars; no gather."""
    return [(jnp.min(x.astype(_F32)), jnp.max(x.astype(_F32))) for x in xs]


@jax.jit
def _moments_jit(xs):
    """Per-field global (min, max, mean, mean-of-squares) of the f32 view —
    the decision-cache fingerprint moments (DESIGN.md §8). Like
    `_minmax_jit`, XLA partitions the reductions shard-locally and
    all-reduces the scalars (the psum reconciliation of DESIGN.md §6), so
    every host derives the identical fingerprint without a gather; min/max
    are reduction-order-free, so the vr this yields matches `_minmax_jit`
    exactly."""
    outs = []
    for x in xs:
        v = x.astype(_F32)
        outs.append((jnp.min(v), jnp.max(v), jnp.mean(v), jnp.mean(v * v)))
    return outs


def _moments_fingerprint(
    view_shape: tuple[int, ...], vr: float, size: int,
    lo: float, hi: float, mean: float, msq: float, r_sp: float,
) -> dict:
    """Fingerprint record for an engine-eligible field, from the global
    value moments. Weaker than the host path's block-content digest — a
    hit certifies the global min/max/mean/mean-square (and the sample
    grid via view shape + r_sp) are unchanged, not the bytes — but any
    decision it replays still honors the policy's pointwise bound on the
    CURRENT data (the codecs guarantee `eb_abs` for whatever they encode;
    DESIGN.md §8.3), so drift past the moments can only cost rate
    optimality, never correctness."""
    h = hashlib.blake2b(digest_size=16)
    h.update(b"repro-dc1-moments")
    h.update(np.asarray(view_shape, np.int64).tobytes())
    h.update(
        np.asarray(
            [vr, float(size), r_sp, lo, hi, mean, msq], np.float64
        ).tobytes()
    )
    return dict(
        kind="moments", digest=h.hexdigest(), vr=vr, size=int(size),
        smin=lo, smax=hi, mean=mean, msq=msq,
    )


# ---------------------------------------------------------------------------
# plan_tree: decisions for a whole pytree, shard-locally
# ---------------------------------------------------------------------------


@dataclass
class FieldPlan:
    """One field's reconciled decision + the layout its bytes will ride."""

    selection: Selection
    solution: ctl.TargetSolution | None
    layout: FieldLayout | None  # None -> single gathered segment
    view_shape: tuple[int, ...]
    reconcile: str  # 'stats' | 'samples' | 'host' | 'degenerate' | 'cached'

    @property
    def sharded(self) -> bool:
        return self.layout is not None


def _shape_shim(view_shape: tuple[int, ...]) -> Any:
    size = int(np.prod(view_shape)) if view_shape else 1
    return SimpleNamespace(ndim=len(view_shape), shape=view_shape, size=size)


def _view_of(x: np.ndarray) -> np.ndarray:
    view = _fold_ndim(np.asarray(x, dtype=np.float32))
    return view.reshape(1) if view.ndim == 0 else view


def plan_tree(
    arrs: list,
    policy: Policy | str | None = None,
    *,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    target_psnr: float | None = None,
    target_ratio: float | None = None,
    r_sp: float | None = None,
    transform: str = "zfp",
    reconcile: str = "auto",
    cache=None,
    names=None,
) -> list[FieldPlan]:
    """Algorithm 1 (or a §7 target solve) over MANY possibly-sharded fields
    without gathering any of them, under ONE quality `Policy`
    (`core/policy.py` — mixed trees group by policy upstream in
    `compress_pytree`/the checkpoint writer and call this per group). The
    legacy mode-string + kwarg spelling shims onto the equivalent Policy
    with a `DeprecationWarning`.

    reconcile='auto' uses the in-graph sufficient-statistics psum for
    fixed_accuracy and the sample-block gather (bit-identical decisions)
    for the target modes; 'stats' / 'samples' force a strategy for
    fixed_accuracy ('stats' is invalid for target modes — the §7 secant
    needs the sampled curves). The §7.4 metric modes (fixed_ssim /
    fixed_correlation / fixed_ks) need no extra collectives: their
    sufficient statistics (sample variance + the sorted value sample for
    the KS quantization curve, `core/quality.py`) are derived from the
    SAME device-extracted halo blocks the secant already gathers, so
    metric solves decide bit-identically to the host path and the warm
    path guards them with the psum-reconciled moments fingerprint like
    any other target mode. Fields whose sharding the engine cannot
    carry (see `analyze`) gather and ride the ordinary host path; their
    decisions are by definition the unsharded ones.

    `cache`/`names` (a `DecisionCache`, DESIGN.md §8): engine-eligible
    fields fingerprint on psum-reconciled global value moments (one
    `_moments_jit` launch replaces the min/max launch — every host derives
    the same fingerprint, so shard-local saves share the cache); validated
    hits skip the engine launch entirely (`reconcile='cached'`).
    Host-fallback and degenerate fields bypass the cache and re-decide
    every call."""
    if isinstance(policy, Policy):
        if any(v is not None for v in (eb_abs, eb_rel, target_psnr, target_ratio, r_sp)):
            raise ValueError("pass either a Policy or the legacy kwargs, not both")
    elif policy is None or isinstance(policy, str):
        policy = policy_from_kwargs(
            "plan_tree", mode=policy, eb_abs=eb_abs, eb_rel=eb_rel,
            target_psnr=target_psnr, target_ratio=target_ratio, r_sp=r_sp,
        )
    else:
        raise TypeError(f"expected a Policy (or legacy mode str), got {policy!r}")
    mode, r_sp = policy.mode, policy.r_sp
    eb_abs, eb_rel = policy.eb_abs, policy.eb_rel
    codecs = policy.codecs
    if mode == "raw":
        raise ValueError("plan_tree has nothing to decide for Policy.raw()")
    if mode != "fixed_accuracy":
        if reconcile == "stats":
            raise ValueError("target modes require reconcile='samples'")
        reconcile_eff = "samples"
    else:
        reconcile_eff = "stats" if reconcile in ("auto", "stats") else "samples"
    if mode == "fixed_accuracy":
        target = eb_abs if eb_abs is not None else eb_rel
    else:
        attr = TARGET_FIELD.get(mode)
        if attr is None:
            raise ValueError(
                f"plan_tree cannot solve mode {mode!r}; supported modes: "
                f"fixed_accuracy, {', '.join(TARGET_FIELD)}"
            )
        target = float(getattr(policy, attr))

    arrs = list(arrs)
    n = len(arrs)
    if cache is not None:
        if names is None:
            raise ValueError("plan_tree(cache=...) requires names=")
        names = list(names)
        if len(names) != n:
            raise ValueError(
                f"names/arrs length mismatch: {len(names)} vs {n}"
            )
    plans: list[FieldPlan | None] = [None] * n
    layouts = [analyze(x) for x in arrs]
    # one global min/max launch for every engine-eligible field (size-0
    # fields have no reduction identity and pin vr = 0.0, like the host
    # path); the warm path widens it to the fingerprint moments launch
    vr_of: dict[int, float] = {
        i: 0.0 for i in range(n) if layouts[i] is not None and not np.size(arrs[i])
    }
    moments_of: dict[int, tuple[float, float, float, float]] = {}
    elig = [i for i in range(n) if layouts[i] is not None and i not in vr_of]
    if elig and cache is None:
        mm = jax.device_get(_minmax_jit([arrs[i] for i in elig]))
        for i, (lo, hi) in zip(elig, mm):
            # f32 subtraction first, matching the unsharded host path
            vr_of[i] = float(np.float32(hi) - np.float32(lo))
    elif elig:
        mm = jax.device_get(_moments_jit([arrs[i] for i in elig]))
        for i, (lo, hi, mean, msq) in zip(elig, mm):
            vr_of[i] = float(np.float32(hi) - np.float32(lo))
            moments_of[i] = (float(lo), float(hi), float(mean), float(msq))

    cache_store: list[tuple[int, str, tuple, str, dict]] = []
    host_idx: list[int] = []
    engine: list[tuple[int, np.ndarray]] = []  # (field index, global starts)
    for i, x in enumerate(arrs):
        lay = layouts[i]
        if lay is None:
            host_idx.append(i)
            continue
        view_shape = lay.view_shape
        vr = vr_of[i]
        # target modes mirror solve_many's degenerate handling: no bound
        # hints reach the raw fallback (eb defaults to 1e-3 * vr there)
        deg_eb = (eb_abs, eb_rel) if mode == "fixed_accuracy" else (None, None)
        sel0 = _degenerate_selection(_shape_shim(view_shape), vr, *deg_eb, r_sp)
        if sel0 is not None:
            sol = None
            if mode != "fixed_accuracy":
                # raw storage is exact: every quality floor is met (PSNR,
                # SSIM, correlation, KS) — only fixed_ratio misses target
                sol = ctl.TargetSolution(
                    sel0, mode, float(target), math.inf, ctl.RAW_BITS,
                    mode != "fixed_ratio",
                    est_metric=qual.lossless_metric(mode),
                )
            plans[i] = FieldPlan(sel0, sol, lay, view_shape, "degenerate")
            continue
        starts = est.block_starts(view_shape, r_sp)
        cap = _max_batch_blocks(len(view_shape))
        if len(starts) > cap:
            if mode == "fixed_accuracy":
                host_idx.append(i)  # select_many's monster-field fallback
                continue
            starts = starts[:: -(-len(starts) // cap)]  # controller's stride-down
        if cache is not None:
            shape = tuple(int(s) for s in np.shape(x))
            dtype = str(x.dtype)
            fp = _moments_fingerprint(
                view_shape, vr, int(np.prod(view_shape)), *moments_of[i], r_sp
            )
            entry = cache.lookup(names[i], shape, dtype, policy, transform, fp)
            if entry is not None and (
                mode == "fixed_accuracy" or entry.solution is not None
            ):
                sol = entry.to_solution() if entry.solution is not None else None
                plans[i] = FieldPlan(
                    entry.to_selection(), sol, lay, view_shape, "cached"
                )
                continue
            cache_store.append((i, names[i], shape, dtype, fp))
        engine.append((i, starts))

    # device-extracted sample blocks per engine field (samples mode), or
    # in-graph stats decisions written straight into `plans` (stats mode)
    blocks_of: dict[int, np.ndarray] = {}
    if engine:
        mesh_groups: dict[Mesh, list[tuple[int, np.ndarray]]] = {}
        for i, starts in engine:
            mesh_groups.setdefault(layouts[i].mesh, []).append((i, starts))
        for mesh, group in mesh_groups.items():
            _plan_engine_group(
                mesh, group, arrs, layouts, vr_of, plans, blocks_of, mode,
                float(target), eb_abs, eb_rel, r_sp, transform, reconcile_eff,
                codecs,
            )

    # Decide everything not yet planned in ONE merged batch run: host-side
    # members are gathered by the same helpers `select_many`/`solve_many`
    # use, engine members carry their device-extracted blocks, and merging
    # them in input order reproduces the unsharded batch composition
    # exactly — so mixed eligible/fallback pytrees still decide
    # bit-identically (the f32 cross-field reductions see the same packing).
    # host-fallback members gather to host; on a multi-process mesh the
    # fetch rides a replicating computation (`dist.to_numpy`) so every
    # host sees the identical array and derives the identical decision
    host_arrs = [dist.to_numpy(arrs[i]) for i in host_idx]
    if mode == "fixed_accuracy":
        results: list[Selection | None] = [None] * n
        if reconcile_eff == "samples" or host_idx:
            groups = select_mod._build_select_members(
                host_arrs, host_idx, results, eb_abs, eb_rel, r_sp, transform,
                codecs,
            )
            for i, blocks in blocks_of.items():
                lay = layouts[i]
                eb = float(eb_abs) if eb_abs is not None else float((eb_rel or 0.0) * vr_of[i])
                groups.setdefault(len(lay.view_shape), []).append(
                    (i, blocks, eb, vr_of[i], int(np.prod(lay.view_shape)))
                )
            for nd in groups:
                groups[nd].sort(key=lambda m: m[0])
            _run_select_batches(groups, results, r_sp, transform, codecs)
        for i in host_idx:
            plans[i] = FieldPlan(
                results[i], None, None, _host_view_shape(arrs[i]), "host"
            )
        for i in blocks_of:
            plans[i] = FieldPlan(
                results[i], None, layouts[i], layouts[i].view_shape, "samples"
            )
    else:
        results_t: list[ctl.TargetSolution | None] = [None] * n
        groups_t = ctl._build_solve_members(
            host_arrs, host_idx, results_t, mode, float(target), r_sp
        )
        for i, blocks in blocks_of.items():
            lay = layouts[i]
            groups_t.setdefault(len(lay.view_shape), []).append(
                ctl._Member(i, blocks, vr_of[i], int(np.prod(lay.view_shape)))
            )
        for nd in groups_t:
            groups_t[nd].sort(key=lambda m: m.idx)
        ctl._solve_groups(
            groups_t, results_t, mode, float(target), ctl.DEFAULT_ROUNDS[mode],
            r_sp, transform, codecs,
        )
        for i in host_idx:
            sol = results_t[i]
            plans[i] = FieldPlan(
                sol.selection, sol, None, _host_view_shape(arrs[i]), "host"
            )
        for i in blocks_of:
            sol = results_t[i]
            plans[i] = FieldPlan(
                sol.selection, sol, layouts[i], layouts[i].view_shape, "samples"
            )
    for i, name, shape, dtype, fp in cache_store:
        plan = plans[i]
        cache.store(
            name, shape, dtype, policy, transform, fp, plan.selection,
            solution=plan.solution,
        )
    return plans  # type: ignore[return-value]


def _host_view_shape(arr: np.ndarray) -> tuple[int, ...]:
    """Folded-view shape without materializing the f32 view (0-d -> (1,))."""
    vs = fold_plan(tuple(int(s) for s in np.shape(arr)))[0]
    return vs if vs else (1,)


def _plan_engine_group(
    mesh: Mesh,
    group: list[tuple[int, np.ndarray]],
    arrs: list,
    layouts: list,
    vr_of: dict[int, float],
    plans: list,
    blocks_of: dict[int, np.ndarray],
    mode: str,
    target: float,
    eb_abs: float | None,
    eb_rel: float | None,
    r_sp: float,
    transform: str,
    reconcile_eff: str,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> None:
    """Run one engine launch over the eligible fields of one mesh: stats
    mode writes finished plans; samples mode deposits the reassembled
    global-order blocks into `blocks_of` for the caller's merged batch run."""
    descs, stacked, ebs, vrs, sizes, owned_of = [], [], [], [], [], []
    for i, starts in group:
        lay: FieldLayout = layouts[i]
        starts = np.ascontiguousarray(np.asarray(starts, np.int64))
        owned, mx, stacked_i = _starts_plan(lay, starts.tobytes(), len(starts))
        stacked.append(stacked_i)
        local_orig = tuple(
            int(np.shape(arrs[i])[d])
            // (int(mesh.shape[e]) if isinstance(e, str) else 1)
            for d, e in enumerate(lay.orig_spec)
        )
        descs.append(
            _FieldDesc(local_orig, lay.orig_spec, lay.view_shape, lay.local_view, lay.axis_of_dim, mx)
        )
        vr = vr_of[i]
        eb = float(eb_abs) if eb_abs is not None else float((eb_rel or 0.0) * vr)
        ebs.append(eb)
        vrs.append(np.float32(vr))
        sizes.append(np.float32(int(np.prod(lay.view_shape))))
        owned_of.append((i, starts, owned))
    fn = _engine_fn(
        mesh,
        tuple(descs),
        "stats" if reconcile_eff == "stats" else "samples",
        transform,
        replicate_out=reconcile_eff != "stats" and dist.spans_processes(mesh),
    )
    xs = tuple(arrs[i] for i, _ in group)
    args = (
        xs,
        tuple(stacked),
        jnp.asarray(np.asarray(ebs, np.float32)),
        jnp.asarray(np.asarray(vrs, np.float32)),
        jnp.asarray(np.asarray(sizes, np.float32)),
    )
    if reconcile_eff == "stats":
        stats = jax.device_get(fn(*args))
        for (i, _, _), (br_sz, br_zfp, psnr, eb_sz), eb in zip(owned_of, stats, ebs):
            bs, bz = float(br_sz), float(br_zfp)
            codec = _pick_codec(bs, bz, codecs)
            sel = Selection(
                codec, float(eb), float(eb_sz), bs, bz, float(psnr), vr_of[i], r_sp
            )
            plans[i] = FieldPlan(sel, None, layouts[i], layouts[i].view_shape, "stats")
        return
    blocks_g, slots_g = fn(*args)
    # reassemble each field's sample blocks in GLOBAL block order — after
    # this, inputs to the deciders are bit-identical to the unsharded
    # host-gathered ones; the caller merges them with any host members
    for (i, starts, _), bl, sl in zip(owned_of, blocks_g, slots_g):
        bl = np.asarray(bl)
        sl = np.asarray(sl)
        keep = sl >= 0
        out = np.zeros((len(starts),) + bl.shape[1:], np.float32)
        out[sl[keep]] = bl[keep]
        blocks_of[i] = out


# ---------------------------------------------------------------------------
# Per-shard encoding / segment assembly (Step 4, shard-locally)
# ---------------------------------------------------------------------------


@dataclass
class Segment:
    """One encoded shard of a field: `data` covers view[start:stop]."""

    start: tuple[int, ...]
    stop: tuple[int, ...]
    codec: str
    data: bytes


def _local_device(devices: tuple) -> Any:
    """The replica device THIS process can address (multi-process jobs hold
    only their own shards; single-process emulation addresses all). The
    multi-host segment writer (`checkpoint/manager.py`, DESIGN.md §6.2)
    only ever asks for shards it OWNS (`dist.owner_host`), and the owner
    holds a replica by construction, so this raising means a caller
    skipped the ownership filter."""
    for d in devices:
        if getattr(d, "process_index", 0) == jax.process_index():
            return d
    raise ValueError(
        "no addressable replica of this shard on this process — fetch only "
        "segments owned by this host (dist.owner_host; DESIGN.md §6.2)"
    )


def encode_view_segment(
    view32: np.ndarray, sel: Selection, *, device_encode: bool = False
) -> tuple[str, bytes]:
    """Step 4 on one (shard of a) folded f32 view, mirroring
    `selector.encode_with_selection` including the never-bigger-than-raw
    safety net — applied per shard, so an incompressible shard of a
    compressible field degrades alone (DESIGN.md §6). Dispatches through
    the codec registry (DESIGN.md §2.1); with `device_encode`, codecs
    advertising the capability finish Stage III in-graph first and the
    host coder only runs when the device tier declines (DESIGN.md §3.7)."""
    if sel.codec == "raw":
        return "raw", view32.tobytes()
    codec = _codecs.get(sel.codec)
    data = None
    if device_encode and getattr(codec, "device_encode", False):
        data = codec.encode_device(view32, sel)
    if data is None:
        data = codec.encode(view32, sel)
    if len(data) >= view32.nbytes:
        return "raw", view32.tobytes()
    return sel.codec, data


def encode_plan(
    x: Any,
    plan: FieldPlan,
    host: int | None = None,
    *,
    device_encode: bool = False,
) -> list[Segment]:
    """Encode one field's bytes under its plan: per unique shard when the
    layout allows (each host touches only bytes it already holds), one
    gathered segment otherwise. Shard encoding reconstructs bit-identically
    to whole-field encoding because SZ's reconstruction is elementwise
    (`round(x/delta)*delta`) and ZFP's is 4-block-local with 4-aligned
    shard boundaries.

    `host=None` (single-controller) encodes EVERY segment. With a host
    index, only the segments that host OWNS are encoded — a replicated
    shard is written exactly once, by the process holding its lowest-id
    replica (`dist.owner_host`, the same rule on every host, so the
    per-host partition needs no coordination); gather-fallback fields
    write their single segment on host 0 (DESIGN.md §6.2)."""
    sel = plan.selection
    if not plan.sharded:
        if host is not None and host != 0:
            return []
        view = _view_of(dist.to_numpy(x))
        codec, data = encode_view_segment(view, sel, device_encode=device_encode)
        return [Segment((0,) * view.ndim, view.shape, codec, data)]
    segs = []
    for s in plan.layout.segs:
        if host is not None and dist.owner_host(s.devices) != host:
            continue
        local = rsh.shard_data(x, _local_device(s.devices))
        view = np.asarray(local, dtype=np.float32).reshape(
            tuple(b - a for a, b in zip(s.start, s.stop))
        )
        codec, data = encode_view_segment(view, sel, device_encode=device_encode)
        segs.append(Segment(s.start, s.stop, codec, data))
    return segs


def field_codec(sel_codec: str, segments: list) -> str:
    """The codec to RECORD for a field: the global decision bit, demoted
    to 'raw' when EVERY segment hit the never-bigger-than-raw safety net —
    mirroring the unsharded `encode_with_selection`, which rewrites the
    field codec when the whole stream failed to beat raw. Mixed outcomes
    keep the decision bit; the per-segment codecs in the manifest stay
    authoritative for decoding either way. Accepts `Segment`s or bare
    codec strings — the multi-host manifest assembler (DESIGN.md §6.2)
    evaluates the demote over the segment rows MERGED from every host's
    table, so the recorded codec matches the single-controller writer."""
    seg_codecs = [getattr(s, "codec", s) for s in segments]
    if sel_codec != "raw" and seg_codecs and all(c == "raw" for c in seg_codecs):
        return "raw"
    return sel_codec


def decode_segments(
    view_shape: tuple[int, ...], segments: list[Segment]
) -> np.ndarray:
    """Reassemble a field's f32 view from its (possibly per-shard) encoded
    segments — the elastic-restore core: any mesh (or none) can consume
    the result by resharding."""
    out = np.empty(view_shape, np.float32)
    for s in segments:
        extent = tuple(b - a for a, b in zip(s.start, s.stop))
        part = _codecs.get(s.codec).decode(s.data)
        out[tuple(slice(a, b) for a, b in zip(s.start, s.stop))] = part.reshape(extent)
    return out


__all__ = [
    "FieldLayout",
    "FieldPlan",
    "Segment",
    "ShardSeg",
    "analyze",
    "decode_segments",
    "encode_plan",
    "encode_view_segment",
    "plan_tree",
]
