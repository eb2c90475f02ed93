"""Public compression API: fields and pytrees (DESIGN.md §2, §7).

A "field" (paper's unit of selection — one simulation variable) maps to one
named tensor. Quality travels as a `Policy` object (`core/policy.py`) —
ONE validated value carrying the mode, its target, the estimator sampling
rate, and the codec allowlist — instead of a spray of per-call kwargs:

* ``Policy.fixed_accuracy(eb_rel=...)`` / ``(eb_abs=...)`` — the paper's
  bound-centric contract: a pointwise error bound, Algorithm 1 picks the
  cheaper codec at that bound (DESIGN.md §1).
* ``Policy.fixed_psnr(db)`` — the quality-target controller (DESIGN.md §7)
  solves for the per-field bound that lands on the target dB.
* ``Policy.fixed_ratio(x)`` — the controller solves for the bound whose
  estimated rate meets the byte budget (x vs 32-bit raw).
* ``Policy.fixed_ssim(s)`` / ``Policy.fixed_correlation(rho)`` /
  ``Policy.fixed_ks(d)`` — the §7.4 quality-metric targets: the
  controller inverts the per-field metric curve (`core/quality.py`) to an
  equivalent-PSNR target and solves that with the same machinery — SSIM
  and correlation are floors, KS a ceiling, all with zero trial
  compressions.
* ``Policy.raw()`` — store verbatim (exact bytes, original dtype).

`compress_pytree` additionally takes a `PolicySet` — ordered
first-match-wins name rules over a default — so one tree can mix
contracts per leaf ("weights at eb_rel 1e-4, optimizer state at 8x").
Leaves are *grouped by resolved policy* and each group rides one packed
`select_many` / `solve_many` batch, so the single-policy tree still makes
every decision in one estimator launch (bit-identical to the pre-policy
API) and the pow2 jit bucketing of DESIGN.md §1 keeps the compile cache
hitting across groups.

The legacy keyword spelling (`mode=`, `eb_rel=`, `target_psnr=`, ...)
keeps working through a shim that maps it onto the equivalent `Policy`
and emits `DeprecationWarning`.

`compress_pytree` runs the resolved policy per leaf and returns the
compressed fields + the selection-bit stream, exactly the paper's
{C_i, s_i} output.
"""

from __future__ import annotations

import os
import warnings
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable

import jax
import numpy as np

from . import controller as _controller
from .policy import (
    Policy,
    PolicySet,
    as_policy_set,
    group_by_policy,
    policy_from_kwargs,
)
from .selector import (
    CompressedField,
    Selection,
    compression_ratio,
    decompress,
    encode_with_selection,
    select,
    select_and_compress,
    select_many,
)


def _dtype_itemsize(dtype: str) -> int:
    """Bytes per value of a recorded dtype string; tolerates extension
    dtypes (bfloat16 & friends) that numpy only knows once ml_dtypes has
    registered them."""
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        import ml_dtypes  # noqa: F401 - import registers the dtypes

        return np.dtype(dtype).itemsize


@dataclass
class ShardedCompressedField:
    """A field compressed shard-by-shard (DESIGN.md §6): the global codec
    decision plus one encoded `Segment` per unique data shard, each covering
    `view[start:stop]` of the folded f32 view. Reconstruction is
    bit-identical to whole-field encoding (SZ is elementwise, ZFP is
    4-block-local and shard boundaries are 4-aligned)."""

    codec: str
    shape: tuple[int, ...]
    dtype: str
    view_shape: tuple[int, ...]
    segments: list
    selection: Selection | None = None

    @property
    def nbytes(self) -> int:
        return sum(len(s.data) for s in self.segments)


@dataclass
class CompressedTree:
    fields: dict[str, CompressedField]
    treedef: Any

    @property
    def selection_bits(self) -> dict[str, str]:
        return {k: v.codec for k, v in self.fields.items()}

    @property
    def nbytes(self) -> int:
        return sum(v.nbytes for v in self.fields.values())

    @property
    def raw_nbytes(self) -> int:
        # the recorded dtype's itemsize, NOT a flat 4 bytes/value: mixed
        # trees carry f64/bf16/int raw leaves whose true footprint `.ratio`
        # must be measured against
        return sum(
            int(np.prod(v.shape)) * _dtype_itemsize(v.dtype)
            for v in self.fields.values()
        )

    @property
    def ratio(self) -> float:
        return self.raw_nbytes / max(self.nbytes, 1)


def _leaf_name(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _default_workers() -> int:
    return max(1, min(8, (os.cpu_count() or 2) - 1))


def _coerce_policy(
    where: str,
    policy,
    mode: str | None,
    eb_rel: float | None,
    eb_abs: float | None,
    target_psnr: float | None,
    target_ratio: float | None,
    r_sp: float | None,
    *,
    allow_set: bool = False,
    stacklevel: int = 4,
):
    """Resolve the (policy, legacy kwargs) pair every public entry point
    accepts: a Policy (or PolicySet where `allow_set`) passes through;
    legacy kwargs — including a bare mode string or a bare float bound in
    the `policy` slot — shim onto an equivalent Policy with a
    `DeprecationWarning`; nothing at all means the historical default
    (fixed_accuracy at eb_rel 1e-4)."""
    legacy = dict(
        mode=mode, eb_rel=eb_rel, eb_abs=eb_abs,
        target_psnr=target_psnr, target_ratio=target_ratio, r_sp=r_sp,
    )
    has_legacy = any(v is not None for v in legacy.values())
    if isinstance(policy, Policy) or (allow_set and isinstance(policy, PolicySet)):
        if has_legacy:
            raise ValueError(
                f"{where}: pass either policy= or the legacy quality kwargs, "
                "not both"
            )
        return policy
    if isinstance(policy, str):  # old positional `mode`
        if legacy["mode"] is not None:
            raise ValueError(f"{where}: mode given twice")
        legacy["mode"] = policy
    elif isinstance(policy, (int, float)):  # old positional `eb_rel`
        if legacy["eb_rel"] is not None:
            raise ValueError(f"{where}: eb_rel given twice")
        legacy["eb_rel"] = float(policy)
    elif policy is not None:
        raise TypeError(
            f"{where}: expected Policy{' | PolicySet' if allow_set else ''}, "
            f"got {type(policy).__name__}"
        )
    elif not has_legacy:
        return Policy.fixed_accuracy()  # the historical default contract
    return policy_from_kwargs(
        where, **legacy, default_eb_rel=1e-4, stacklevel=stacklevel
    )


def _policy_selections(
    arrs: list[np.ndarray], pol: Policy, cache=None, names=None
) -> list[Selection]:
    """Route one policy group of fields through its solver. fixed_accuracy
    keeps the Algorithm 1 fast path (`select_many`); the target modes run
    the controller (DESIGN.md §7) and unwrap its `TargetSolution`s.
    `cache`/`names` thread the warm decision path through either solver
    (DESIGN.md §8)."""
    if pol.mode == "fixed_accuracy":
        return select_many(arrs, policy=pol, cache=cache, names=names)
    sols = _controller.solve_many(arrs, pol, cache=cache, names=names)
    return [s.selection for s in sols]


def compress(
    x: np.ndarray,
    policy: Policy | str | None = None,
    *,
    device_encode: bool = False,
    mode: str | None = None,
    eb_rel: float | None = None,
    eb_abs: float | None = None,
    target_psnr: float | None = None,
    target_ratio: float | None = None,
    r_sp: float | None = None,
) -> CompressedField:
    """Compress one field under a quality policy; returns a `CompressedField`.

    Args:
      x: the field (any shape; evaluated in float32, the codecs' working
        dtype — the original dtype is recorded and restored by
        `decompress`). Ranks above 3 are folded to 3-D.
      policy: the quality contract (`core/policy.py`):
        `Policy.fixed_accuracy(eb_rel=...)` (default, at eb_rel 1e-4) |
        `Policy.fixed_psnr(db)` | `Policy.fixed_ratio(x)` |
        `Policy.fixed_ssim(s)` | `Policy.fixed_correlation(rho)` |
        `Policy.fixed_ks(d)` | `Policy.raw()`. Fixed-accuracy bounds are
        pointwise and guaranteed on every value of the reconstruction
        (`eb_rel` scales by the field's value range); fixed_psnr lands on
        the target dB (not merely above it); fixed_ratio meets the
        estimated byte budget within ~10% with the chosen bound reported
        in `.selection.eb_abs`; the §7.4 metric modes land on the metric
        target within the documented tolerances (`quality.TOLERANCE`),
        SSIM/correlation as floors and KS as a ceiling. The policy's
        `codecs` allowlist restricts
        which registered codecs compete; `r_sp` is the estimator block
        sampling rate (paper default 5%).
      device_encode: finish Stage III in-graph where the selected codec
        supports it (capability `device_encode`, DESIGN.md §3.7): packed
        stream bytes come off the device in one `device_get` instead of
        raw codes riding a host entropy coder. Decisions are unchanged;
        fields the device encoders decline (the §3.7 fallback rules)
        silently take the host coder. Default off.
      mode / eb_rel / eb_abs / target_psnr / target_ratio / r_sp:
        deprecated keyword spelling of the same contract — shimmed onto a
        `Policy` with a `DeprecationWarning`, decisions unchanged.

    Raw fallback: fields that are too small (< 64 values or a dim < 4),
    constant, or NaN/inf-poisoned store verbatim with codec ``raw``; so
    does any field whose estimated rate exceeds 32 bits/value at the
    requested quality, and any stream that fails to beat raw after
    encoding. Raw streams reproduce the input bit-exactly.
    """
    x = np.asarray(x)
    pol = _coerce_policy(
        "compress", policy, mode, eb_rel, eb_abs, target_psnr, target_ratio, r_sp
    )
    if pol.mode == "raw":
        return CompressedField("raw", x.tobytes(), x.shape, str(x.dtype))
    if pol.mode == "fixed_accuracy":
        sel = select(
            x.astype(np.float32), eb_abs=pol.eb_abs, eb_rel=pol.eb_rel,
            r_sp=pol.r_sp, codecs=pol.codecs,
        )
        return encode_with_selection(x, sel, device_encode=device_encode)
    sol = _controller.solve(x.astype(np.float32), pol)
    return encode_with_selection(x, sol.selection, device_encode=device_encode)


def _is_multidevice(leaf: Any) -> bool:
    sharding = getattr(leaf, "sharding", None)
    try:
        return sharding is not None and len(sharding.device_set) > 1
    except Exception:  # noqa: BLE001 - any exotic sharding: stay unsharded
        return False


def _named_leaves_with_policies(
    leaves: list,
    pset: PolicySet,
    predicate: Callable[[str, Any], bool] | None,
    materialize: bool,
) -> tuple[list[tuple[str, Any]], dict[int, Policy]]:
    """Shared leaf walk of the unsharded and sharded tree paths: name every
    leaf, resolve its policy, and keep only float leaves with a non-raw
    policy (that the deprecated `predicate`, when given, accepts) in the
    returned index -> Policy map."""
    named: list[tuple[str, Any]] = []
    pol_of: dict[int, Policy] = {}
    for path, leaf in leaves:
        name = _leaf_name(path)
        if materialize:
            leaf = np.asarray(leaf)
        elif not hasattr(leaf, "dtype"):
            leaf = np.asarray(leaf)
        named.append((name, leaf))
        if predicate is not None and not predicate(name, leaf):
            continue
        if not np.issubdtype(leaf.dtype, np.floating):
            continue
        pol = pset.resolve(name)
        if pol.mode == "raw":
            continue
        pol_of[len(named) - 1] = pol
    return named, pol_of


def compress_pytree(
    tree: Any,
    policy: Policy | PolicySet | float | str | None = None,
    *,
    workers: int | None = None,
    sharded: bool | None = None,
    cache=None,
    device_encode: bool = False,
    eb_rel: float | None = None,
    eb_abs: float | None = None,
    r_sp: float | None = None,
    predicate: Callable[[str, np.ndarray], bool] | None = None,
    mode: str | None = None,
    target_psnr: float | None = None,
    target_ratio: float | None = None,
) -> CompressedTree:
    """Compress every float leaf of `tree` under per-leaf quality policies.

    Args:
      tree: any pytree; leaf names come from the tree path.
      policy: a `Policy` applied to every float leaf, or a `PolicySet`
        resolving one per leaf name (ordered glob/regex rules, first match
        wins, then the default) — e.g.::

            PolicySet(default=Policy.fixed_accuracy(eb_rel=1e-4),
                      rules=[("opt/*", Policy.fixed_ratio(8.0))])

        Defaults to `Policy.fixed_accuracy()` (eb_rel 1e-4). Leaves whose
        resolved policy is `Policy.raw()` — and all non-float leaves —
        ride through raw (exact bytes, original dtype). Per-leaf targets
        are independent: in fixed_psnr every leaf lands on the target dB
        against its own value range; in the §7.4 metric modes
        (fixed_ssim / fixed_correlation / fixed_ks) every leaf lands on
        the metric target against its own sampled statistics; in
        fixed_ratio every compressible leaf meets the ratio, so the
        tree-level ratio can exceed the target when raw-fallback leaves
        are rare and undershoot it when they dominate.
      workers: thread-pool width for the per-field byte encoders (0 forces
        serial; default: cpu-count-bounded). Selection/solving is batched
        regardless: leaves are grouped by resolved policy and each group's
        sampled blocks go through ONE jitted estimator launch per round
        (`select_many`, or the controller sweep of DESIGN.md §7), then
        encoding overlaps on the pool — the paper's per-field independence
        makes both trivially parallel.
      sharded: route sharded `jax.Array` leaves through the shard-local
        engine (DESIGN.md §6): selection statistics are computed per
        device shard under `shard_map` and reconciled with a cheap
        collective — no full-tensor gather — and each leaf is encoded as
        per-shard `Segment`s inside a `ShardedCompressedField`. Decisions
        match the unsharded path (bit-identically for the sample-gather
        reconciliation; see `core/sharded.py`). Default None auto-enables
        when any leaf lives on more than one device; False forces the
        gather path.
      cache: a `DecisionCache` (DESIGN.md §8) carrying per-leaf decisions
        across repeated saves of the same tree. Leaves whose stats
        fingerprint validates replay the previous save's decision —
        bit-identical to the cold path — and skip the estimator launch;
        drifted or new leaves re-decide and refresh their entry. The
        caller owns the cache object and reuses it across calls
        (`CheckpointManager` persists it in the manifest).
      device_encode: finish Stage III in-graph for codecs with the
        `device_encode` capability (DESIGN.md §3.7) — the thread-pool
        encoders fetch packed stream bytes instead of running the host
        entropy coder. Applies on both the gathered and the shard-local
        (`sharded=True`) paths; decisions and manifests are unchanged,
        and declined fields fall back to the host coder per field.
      eb_rel / eb_abs / r_sp / mode / target_psnr / target_ratio /
        predicate: the deprecated kwarg spelling — shimmed onto a `Policy`
        (predicate rejections onto per-leaf raw) with a
        `DeprecationWarning`, decisions unchanged.

    Returns a `CompressedTree`: per-leaf `CompressedField`s (the {C_i}
    streams) plus `.selection_bits` (the {s_i}).
    """
    pol = _coerce_policy(
        "compress_pytree", policy, mode, eb_rel, eb_abs, target_psnr,
        target_ratio, r_sp, allow_set=True,
    )
    pset = as_policy_set(pol)
    if predicate is not None:
        warnings.warn(
            "compress_pytree(predicate=...) is deprecated; use PolicySet "
            "rules mapping rejected names to Policy.raw()",
            DeprecationWarning,
            stacklevel=2,
        )
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    if sharded is None:
        sharded = any(_is_multidevice(leaf) for _, leaf in leaves)
    if sharded:
        return _compress_pytree_sharded(
            leaves, treedef, pset, predicate, workers, cache=cache,
            device_encode=device_encode,
        )
    named, pol_of = _named_leaves_with_policies(
        leaves, pset, predicate, materialize=True
    )
    # original arrays go in; the solvers cast to f32 one field at a time
    sel_of: dict[int, Selection] = {}
    for p, idxs in group_by_policy(pol_of).items():
        sels = _policy_selections(
            [named[i][1] for i in idxs], p, cache=cache,
            names=[named[i][0] for i in idxs] if cache is not None else None,
        )
        sel_of.update(zip(idxs, sels))

    def encode(i: int) -> CompressedField:
        name, arr = named[i]
        if i not in sel_of:
            return CompressedField("raw", arr.tobytes(), arr.shape, str(arr.dtype))
        # original array in: encode_with_selection casts to f32 internally
        # but records the true dtype, so decompress restores it
        return encode_with_selection(arr, sel_of[i], device_encode=device_encode)

    n_workers = _default_workers() if workers is None else workers
    if n_workers > 1 and len(named) > 1:
        with ThreadPoolExecutor(max_workers=n_workers) as ex:
            encoded = list(ex.map(encode, range(len(named))))
    else:
        encoded = [encode(i) for i in range(len(named))]
    fields = {named[i][0]: cf for i, cf in enumerate(encoded)}
    return CompressedTree(fields=fields, treedef=treedef)


def _compress_pytree_sharded(
    leaves: list,
    treedef: Any,
    pset: PolicySet,
    predicate: Callable[[str, Any], bool] | None,
    workers: int | None,
    cache=None,
    device_encode: bool = False,
) -> CompressedTree:
    """The shard-local engine behind `compress_pytree(sharded=True)`: one
    `plan_tree` pass per policy group decides every float leaf without
    gathering it, then per-shard encoders run on the thread pool
    (DESIGN.md §6)."""
    from . import sharded as _sh

    named, pol_of = _named_leaves_with_policies(
        leaves, pset, predicate, materialize=False
    )
    plan_of: dict[int, Any] = {}
    for p, idxs in group_by_policy(pol_of).items():
        plans = _sh.plan_tree(
            [named[i][1] for i in idxs], p, cache=cache,
            names=[named[i][0] for i in idxs] if cache is not None else None,
        )
        plan_of.update(zip(idxs, plans))

    def encode(i: int):
        name, leaf = named[i]
        plan = plan_of.get(i)
        if plan is None:
            arr = np.asarray(leaf)
            return CompressedField("raw", arr.tobytes(), arr.shape, str(arr.dtype))
        segments = _sh.encode_plan(leaf, plan, device_encode=device_encode)
        return ShardedCompressedField(
            _sh.field_codec(plan.selection.codec, segments),
            tuple(int(s) for s in np.shape(leaf)),
            str(leaf.dtype), plan.view_shape, segments, plan.selection,
        )

    n_workers = _default_workers() if workers is None else workers
    if n_workers > 1 and len(named) > 1:
        with ThreadPoolExecutor(max_workers=n_workers) as ex:
            encoded = list(ex.map(encode, range(len(named))))
    else:
        encoded = [encode(i) for i in range(len(named))]
    fields = {named[i][0]: cf for i, cf in enumerate(encoded)}
    return CompressedTree(fields=fields, treedef=treedef)


def decompress_pytree(ct: CompressedTree) -> Any:
    """Invert `compress_pytree`: every lossy leaf reconstructs within its
    solved bound, every raw leaf bit-exactly (original dtype preserved).
    All restored leaves are WRITEABLE arrays — restored trees can be
    trained on in place. Sharded fields reassemble from their per-shard
    segments — on any device count, the elastic-restore contract of
    DESIGN.md §6."""
    from . import sharded as _sh

    def decode(cf) -> np.ndarray:
        if isinstance(cf, ShardedCompressedField):
            view = _sh.decode_segments(cf.view_shape, cf.segments)
            return view.reshape(cf.shape).astype(np.dtype(cf.dtype))
        # `decompress` handles both raw conventions: selection-less raw
        # leaves restore exact original-dtype bytes, everything else
        # decodes through the codec registry (always writeable)
        return decompress(cf)

    fields = list(ct.fields.values())
    if len(fields) > 1:
        # the host decoders spend their time in numpy, which releases the GIL
        with ThreadPoolExecutor(max_workers=_default_workers()) as ex:
            leaves = list(ex.map(decode, fields))
    else:
        leaves = [decode(cf) for cf in fields]
    return jax.tree_util.tree_unflatten(ct.treedef, leaves)


__all__ = [
    "CompressedField",
    "CompressedTree",
    "Policy",
    "PolicySet",
    "ShardedCompressedField",
    "compress",
    "compress_pytree",
    "decompress_pytree",
    "compression_ratio",
    "select_and_compress",
    "decompress",
]
