"""Fault-tolerant checkpointing with the paper's per-field codec selection.

Two layouts, both behind one reader (manifest v3; the `layout` key picks
the reader):

flat (`CheckpointConfig.sharded=False`): tensors are gathered and saved
whole, so a restarted job may reload under ANY device count / mesh
(elastic scaling by gathering):

  <dir>/step_000123/
    manifest.json   # version: 3, layout: "flat"; the Policy/PolicySet
                    # spec; field table (name, codec s_i, shape, dtype,
                    # offset, nbytes, eb, resolved policy); wall time
    data.bin        # concatenated per-field streams (codec registry)
  <dir>/LATEST      # atomic pointer (written last)

segments (`CheckpointConfig.sharded=True`, DESIGN.md §6): the
shard-local engine (`core/sharded.py`) makes every codec decision from
per-shard statistics reconciled with a psum — no full-tensor gather —
and each field is encoded as per-shard *segments*, written to per-host
data files:

  <dir>/step_000123/
    manifest.json      # version: 3, layout: "segments"; per field:
                       # codec, eb, view_shape, resolved policy and a
                       # segment table [{start, stop, codec, host,
                       # offset, nbytes}] in folded-view coordinates;
                       # hosts + per-host completion (byte counts)
    data.<host>.bin    # one per host: that host's segments, concatenated
    segtable.<host>.json  # multi-host only: the host's segment rows,
                       # merged into the manifest by host 0
    commit.<host>      # per-host completion marker, written LAST
  <dir>/LATEST

The segment writer is genuinely **multi-host** (DESIGN.md §6.2): under
`jax.process_count() > 1`, the psum reconciliation makes every process
derive the IDENTICAL per-field decisions, then each process encodes and
writes only the shards it owns (`dist.owner_host` — one writer per
replicated shard, no coordination needed) into its own `data.<host>.bin`
plus a `segtable.<host>.json` row table and a `commit.<host>` marker.
A bounded barrier (`CheckpointConfig.barrier_timeout_s`) fences the
write phase — a dead or straggling host FAILS the save on every live
host instead of hanging the job (after up to `save_retries` bounded
requeues of the write phase under fresh barrier keys, which absorbs
transient stragglers) — after which host 0 merges the segment
tables into one manifest (recording `hosts` and per-host `completion`
byte counts) and atomically promotes the step directory. A save that
dies mid-flight therefore never publishes: the tmp directory is simply
abandoned and the previous step stays restorable. `restore` refuses any
segment manifest whose completion markers are missing or whose data
files are short (`IncompleteCheckpointError`).

Restore is elastic for both layouts: `restore` reassembles full tensors
from whatever segments exist (a segment checkpoint saved on 8 devices
reloads on 1, 4, or 32 — segment reassembly is mesh-free), and
`restore_tree(shardings=...)` re-shards the result onto ANY target mesh.
Pre-policy checkpoints stay readable forever: v1 manifests (no version
key, flat) and v2 manifests (version: 2, segments) dispatch to the same
readers. Every restored leaf is a WRITEABLE array.

Writes are atomic (tmp dir + rename); `keep_n` old checkpoints are pruned;
`async_save` runs serialization+IO off the training thread (the in-situ
model of the paper: compress while the next step computes) and re-raises
any worker exception from `wait()` — encoder failures are never silently
dropped.

Codec selection is batched: ALL lossy fields of one policy group go
through one `select_many`/`solve_many` estimator launch (one padded
block batch, one device round-trip per group) — or one shard-local
`plan_tree` launch in the segment layout — then per-field byte encoding
runs on a `workers`-wide thread pool so encoding of field i overlaps
with encoding of field j and with the sequential writer draining results
in order.

Quality travels as a `Policy` / `PolicySet` (`core/policy.py`,
DESIGN.md §2, §7): `CheckpointConfig.policy` holds the per-tensor
contract — the bound-centric default (``Policy.fixed_accuracy()``),
``Policy.fixed_psnr(db)`` / ``Policy.fixed_ratio(x)`` solved by the
quality-target controller ("every checkpoint is 8x smaller" as a storage
contract), the §7.4 metric targets (``Policy.fixed_ssim(s)`` /
``Policy.fixed_correlation(rho)`` / ``Policy.fixed_ks(d)``), or a
`PolicySet` mixing contracts per tensor name ("weights at eb_rel 1e-4,
`opt/*` at 8x"). Tensors are grouped by resolved policy and each group
rides one batched decision launch. Every target-mode field row records
a `quality` dict (resolved target, estimated PSNR/bitrate/metric,
on_target) in the manifest, so what each tensor was promised — and what
the controller believes it got — audits from the manifest alone.

With a bare `Policy`, weights default to lossy and optimizer state
(`opt/*`) to raw (Adam moments are cheap to compress but sensitive near
zero) via the default `lossy` callable; with a `PolicySet`, the set's
rules govern everything (map `opt/*` to `Policy.raw()` — or to a lossy
policy — yourself). In the segment layout, policy-raw leaves also write per-shard
segments (exact original-dtype bytes, codec ``none``), so optimizer
state never gathers either.

Manifests are **v3**: `layout` ("flat" | "segments") picks the reader,
the top-level `policy` records the configured Policy/PolicySet spec, and
every field row records its *resolved* policy next to the codec and
bound — restore-side tooling can audit exactly what each tensor was
promised. v1 (no version key) and v2 (`version: 2`, segment layout)
checkpoints stay readable behind the same `restore`.

The legacy kwarg spelling (`CheckpointConfig(eb_rel=...)`, `mode=`,
`target_psnr=`, `target_ratio=`, `r_sp=`) shims onto an equivalent
`Policy` with a `DeprecationWarning`; decisions and bytes are unchanged.
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable

import jax
import numpy as np

from repro.core import codecs, controller
from repro.core import selector as sel
from repro.runtime import dist
from repro.core.policy import (
    TARGET_FIELD,
    Policy,
    PolicySet,
    as_policy_set,
    group_by_policy,
    policy_from_kwargs,
    policy_set_spec,
)


class IncompleteCheckpointError(RuntimeError):
    """A segment checkpoint is missing per-host completion markers (or its
    data files are shorter than the recorded byte counts): some host's
    write never finished, so the manifest must not be trusted."""


@dataclasses.dataclass
class CheckpointConfig:
    directory: str
    keep_n: int = 3
    # the quality contract (DESIGN.md §2, §7): one Policy for every lossy
    # tensor, or a PolicySet resolving one per tensor name. Default:
    # Policy.fixed_accuracy() (eb_rel 1e-4).
    policy: Policy | PolicySet | None = None
    compress: bool = True
    workers: int = 4  # thread-pool width for per-field byte encoding (0 = serial)
    # shard-local engine (DESIGN.md §6): decisions from per-shard statistics,
    # per-shard segment encoding, segment-layout manifest — no gather
    sharded: bool = False
    # cross-step decision cache (DESIGN.md §8): False = cold every save
    # (pre-§8 behavior, byte-identical); True = manager-owned
    # `DecisionCache()` (bit-identity contract, tolerance 0); or pass a
    # configured `DecisionCache` instance to share one across managers or
    # to opt into tolerance>0 / warm_start. The cache rides the manifest
    # (`decision_cache` key) so `restore` leaves the next save warm.
    cache: Any = False
    # device-resident Stage III (DESIGN.md §3.7): when True, codecs that
    # advertise the `device_encode` capability pack their bitstreams
    # in-graph and only the packed words cross the interconnect; fields
    # the device tier declines (fallback rules of §3.7) take the host
    # coder, so streams stay byte-identical either way
    device_encode: bool = False
    # multi-host save fencing (DESIGN.md §6.2): how long any host waits at
    # the write/publish barriers before FAILING the save (a straggler or
    # dead host must surface as an exception, never as a hang)
    barrier_timeout_s: float = 120.0
    # bounded requeue on `BarrierTimeout` (DESIGN.md §6.2): a transiently
    # straggling host (GC pause, FS hiccup) fails the attempt on every
    # live host; each retry re-runs the write phase under a FRESH save
    # sequence number — fresh KV barrier keys, so a late arrival at the
    # abandoned attempt's barrier can never satisfy the new one. 0
    # disables. The count actually used is `manager.last_save_retries`
    # (and `thread.save_result["retries"]` for async saves).
    save_retries: int = 1
    # deprecated kwarg spelling (None = unset) — shimmed onto `policy`
    eb_rel: float | None = None
    r_sp: float | None = None
    mode: str | None = None
    target_psnr: float | None = None
    target_ratio: float | None = None

    def __post_init__(self):
        if isinstance(self.policy, (int, float)):
            # old positional `eb_rel` in the policy slot
            if self.eb_rel is not None:
                raise ValueError("CheckpointConfig: eb_rel given twice")
            self.eb_rel, self.policy = float(self.policy), None
        legacy = (self.eb_rel, self.r_sp, self.mode, self.target_psnr, self.target_ratio)
        if any(v is not None for v in legacy):
            if self.policy is not None:
                raise ValueError(
                    "CheckpointConfig: pass either policy= or the legacy "
                    "quality kwargs, not both"
                )
            self.policy = policy_from_kwargs(
                "CheckpointConfig", mode=self.mode, eb_rel=self.eb_rel,
                target_psnr=self.target_psnr, target_ratio=self.target_ratio,
                r_sp=self.r_sp, default_eb_rel=1e-4, stacklevel=4,
            )
        elif self.policy is None:
            self.policy = Policy.fixed_accuracy()

    @property
    def policy_set(self) -> PolicySet:
        return as_policy_set(self.policy)


def _leaf_items(tree: Any) -> list[tuple[str, np.ndarray]]:
    """Host copies of every leaf. `dist.to_numpy` replicates leaves this
    process cannot fully address (a collective — in a multi-process job
    every host must walk the same tree at the same point), so the flat
    layout stays usable beyond one process: decisions are derived from
    identical gathered arrays on every host and host 0 alone writes."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((name, dist.to_numpy(leaf)))
    return out


def _leaf_items_raw(tree: Any) -> list[tuple[str, Any]]:
    """Like `_leaf_items` but WITHOUT materializing leaves on host — the
    sharded writer must see the original jax.Arrays to reach their shards."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in leaves:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        if not hasattr(leaf, "dtype"):
            leaf = np.asarray(leaf)
        out.append((name, leaf))
    return out


def _treedef_of(tree: Any):
    return jax.tree_util.tree_structure(tree)


#: spec recorded for leaves that ride raw (non-float, lossy-rejected, or
#: policy-raw) — the manifest row's `policy` key is always present in v3
_RAW_SPEC = {"mode": "raw"}


def _field_policy_spec(pol: Policy | None) -> dict:
    return pol.spec() if pol is not None else dict(_RAW_SPEC)


def _quality_record(sol: Any) -> dict | None:
    """Manifest field row `quality` key for a §7 target solve: the resolved
    target next to what the controller estimates it achieved — restore-side
    tooling can audit the quality contract per tensor without re-deciding.
    `est_metric` appears only for the §7.4 metric modes (fixed_ssim /
    fixed_correlation / fixed_ks); None for fixed_accuracy/raw rows (no
    solve happened, the bound in `eb` is the whole contract)."""
    if sol is None:
        return None
    rec = dict(
        mode=sol.mode, target=sol.target, est_psnr=sol.est_psnr,
        est_bitrate=sol.est_bitrate, on_target=sol.on_target,
    )
    if sol.est_metric is not None:
        rec["est_metric"] = sol.est_metric
    return rec


class _HostBlobs:
    """Range reader over a step directory's per-host data files: a host's
    file is opened on first touch and only the spans asked for are read —
    the elastic restore's locality primitive (a process restoring its own
    shards never reads bytes from a data file it doesn't need)."""

    def __init__(self, d: str):
        self._d = d
        self._files: dict[int, Any] = {}

    def read(self, host: int, offset: int, nbytes: int) -> bytes:
        f = self._files.get(host)
        if f is None:
            f = self._files[host] = open(
                os.path.join(self._d, f"data.{host}.bin"), "rb"
            )
        f.seek(offset)
        return f.read(nbytes)

    @property
    def hosts_opened(self) -> list[int]:
        return sorted(self._files)

    def close(self) -> None:
        for f in self._files.values():
            f.close()
        self._files.clear()


def _flat_span(
    start: tuple, stop: tuple, shape: tuple[int, ...]
) -> tuple[int, int]:
    """Conservative C-order flat element range [lo, hi) bounding the box
    start:stop of an array of `shape`. The fold (`core/sharded.fold_plan`)
    only merges adjacent dims — a pure C-order reshape — so spans computed
    in ORIGINAL and FOLDED coordinates index the same flat element order
    and are directly comparable: the basis of restore-side segment
    filtering. Conservative means a span may cover extra elements (a box
    is not flat-contiguous), never fewer — a needed segment is never
    skipped."""
    if not shape:
        return 0, 1
    if any(int(b) <= int(a) for a, b in zip(start, stop)):
        return 0, 0
    lo = int(np.ravel_multi_index(tuple(int(a) for a in start), shape))
    hi = int(np.ravel_multi_index(tuple(int(b) - 1 for b in stop), shape)) + 1
    return lo, hi


def _spans_overlap(a: tuple[int, int], b: tuple[int, int]) -> bool:
    return a[0] < b[1] and b[0] < a[1]


def _need_span(sharding: Any, shape: tuple[int, ...]) -> tuple[int, int]:
    """The conservative flat span of the elements THIS process must hold
    under a target `sharding`: the union bounding range of its addressable
    shards' index boxes. (0, 0) when no shard of the field lands here."""
    try:
        imap = sharding.devices_indices_map(tuple(shape))
    except Exception:
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        return 0, size
    pid = dist.process_index()
    lo = hi = None
    for dev, idx in imap.items():
        if int(getattr(dev, "process_index", 0)) != pid:
            continue
        start, stop = [], []
        for sl, dim in zip(idx, shape):
            a, b, _ = sl.indices(dim)
            start.append(a)
            stop.append(b)
        a, b = _flat_span(tuple(start), tuple(stop), tuple(shape))
        lo = a if lo is None else min(lo, a)
        hi = b if hi is None else max(hi, b)
    if lo is None:
        return 0, 0
    return lo, hi


class CheckpointManager:
    def __init__(self, cfg: CheckpointConfig):
        self.cfg = cfg
        os.makedirs(cfg.directory, exist_ok=True)
        self._thread: threading.Thread | None = None
        self._exc: BaseException | None = None
        # per-manager save counter: barrier names must be fresh per save
        # (re-saving one step would otherwise reuse a consumed barrier);
        # SPMD symmetry keeps it in lockstep on every host
        self._save_seq = 0
        # segment locality of the last multi-host `restore_tree` (tests +
        # ops introspection): {"segments_decoded", "segments_total",
        # "hosts_opened"}
        self.last_restore_stats: dict | None = None
        # BarrierTimeout requeues the last completed save needed (§6.2)
        self.last_save_retries = 0
        # resolve cfg.cache -> DecisionCache | None (DESIGN.md §8)
        cache = cfg.cache
        if cache is True:
            from repro.core.decision_cache import DecisionCache

            cache = DecisionCache()
        elif cache is False or cache is None:
            cache = None
        self.cache = cache

    # -- save ---------------------------------------------------------------

    def _default_lossy(self) -> Callable[[str], bool]:
        """With a bare Policy, optimizer state (`opt/*`) defaults to raw;
        with a PolicySet the rules govern raw-ness themselves, so every
        eligible leaf goes through policy resolution."""
        if isinstance(self.cfg.policy, PolicySet):
            return lambda name: True
        return lambda name: not name.startswith("opt/")

    def _resolve_policies(
        self, items: list, lossy: Callable[[str], bool]
    ) -> dict[int, Policy]:
        """index -> resolved Policy for every leaf that will compress:
        float, >= 64 values, accepted by `lossy`, and not policy-raw."""
        cfg = self.cfg
        pset = cfg.policy_set
        pol_of: dict[int, Policy] = {}
        for i, (name, leaf) in enumerate(items):
            if not (
                cfg.compress
                and lossy(name)
                and np.issubdtype(leaf.dtype, np.floating)
                and leaf.size >= 64
            ):
                continue
            pol = pset.resolve(name)
            if pol.mode == "raw":
                continue
            pol_of[i] = pol
        return pol_of

    def _retry_barrier_timeout(self, attempt_fn: Callable[[], str]) -> str:
        """Bounded `BarrierTimeout` requeue (DESIGN.md §6.2). Each attempt
        consumes its own `_save_seq` value — the counter stays in lockstep
        on every host (all hosts run the same attempt loop), so the retry's
        KV barrier keys (`ckpt:{step}:{seq}:*`) are fresh on every host and
        a straggler arriving late at an abandoned attempt's barrier cannot
        satisfy the new one. Only the write/publish phase is retried —
        device collectives (plan/gather) run once, upstream. Exhausting
        `cfg.save_retries` re-raises the timeout: a persistently dead host
        must fail the save, not loop. `last_save_retries` records how many
        requeues the returning attempt needed."""
        retries = max(0, int(self.cfg.save_retries))
        self.last_save_retries = 0
        for attempt in range(retries + 1):
            try:
                return attempt_fn()
            except dist.BarrierTimeout:
                if attempt >= retries:
                    raise
                self.last_save_retries = attempt + 1
        raise AssertionError("unreachable")

    def save(self, step: int, tree: Any, lossy: Callable[[str], bool] | None = None) -> str:
        """Synchronous atomic save. Each tensor's quality policy comes from
        `cfg.policy` (a `PolicySet` resolves per name); `lossy(name)` is a
        hard per-call override forcing names to raw (default: with a bare
        Policy, float leaves under 'opt/' ride raw). With `cfg.sharded`,
        writes the per-shard segment layout via the shard-local engine
        (DESIGN.md §6) — no full-tensor gather. Saves that die at a
        multi-host barrier are requeued up to `cfg.save_retries` times
        under fresh barrier keys before the `BarrierTimeout` surfaces."""
        if lossy is None:
            lossy = self._default_lossy()
        if self.cfg.sharded:
            return self._save_sharded(step, tree, lossy)
        return self._retry_barrier_timeout(
            lambda: self._save_flat(step, tree, lossy)
        )

    def _save_flat(self, step: int, tree: Any, lossy: Callable[[str], bool]) -> str:
        """One attempt of the flat (gathered) writer — `save` wraps it in
        the bounded BarrierTimeout requeue. `_leaf_items` is a collective
        only for leaves not yet on host; the async path materializes the
        snapshot on the calling thread first, so a worker-thread retry
        re-walks plain host arrays."""
        cfg = self.cfg
        final = os.path.join(cfg.directory, f"step_{step:09d}")
        t0 = time.time()
        # the gather (a collective beyond one process) runs on EVERY host;
        # selection + writing then run on host 0 alone — flat multi-host
        # saves are correct but gather-bound, sharded=True is the one that
        # scales (DESIGN.md §6.2)
        items = _leaf_items(tree)
        seq = self._save_seq
        self._save_seq += 1
        if dist.process_index() != 0:
            dist.barrier(
                f"ckpt:{step}:{seq}:published", self.cfg.barrier_timeout_s
            )
            return final
        tmp = os.path.join(cfg.directory, f".tmp_step_{step:09d}_{os.getpid()}")
        os.makedirs(tmp, exist_ok=True)
        fields = []
        pol_of = self._resolve_policies(items, lossy)
        # Steps 1-3 for every lossy field in ONE batched estimator launch
        # per round AND policy group (the solvers cast to f32 one field at
        # a time and keep only the sampled blocks, so no full-tree f32
        # copy materializes; a single-policy tree is one group, exactly
        # the pre-policy batch composition)
        sel_of: dict[int, sel.Selection] = {}
        sol_of: dict[int, controller.TargetSolution] = {}
        for pol, idxs in group_by_policy(pol_of).items():
            arrs = [items[i][1] for i in idxs]
            names = [items[i][0] for i in idxs] if self.cache is not None else None
            if pol.mode == "fixed_accuracy":
                sels = sel.select_many(
                    arrs, policy=pol, cache=self.cache, names=names
                )
            else:
                sols = controller.solve_many(
                    arrs, pol, cache=self.cache, names=names
                )
                sol_of.update(zip(idxs, sols))
                sels = [s.selection for s in sols]
            sel_of.update(zip(idxs, sels))

        def _encode(i: int) -> tuple[bytes, str, float]:
            name, arr = items[i]
            s = sel_of.get(i)
            if s is None:
                return arr.tobytes(), "none", 0.0
            cf = sel.encode_with_selection(  # casts to f32 internally
                arr, s, device_encode=self.cfg.device_encode
            )
            return cf.data, cf.codec, s.eb_abs

        with open(os.path.join(tmp, "data.bin"), "wb") as f:
            off = 0
            for i, ((name, arr), (data, codec, eb)) in enumerate(
                zip(items, self._encoded_in_order(items, _encode))
            ):
                f.write(data)
                row = dict(
                    name=name, codec=codec, shape=list(arr.shape),
                    dtype=str(arr.dtype), offset=off, nbytes=len(data), eb=eb,
                    policy=_field_policy_spec(pol_of.get(i)),
                )
                q = _quality_record(sol_of.get(i))
                if q is not None:
                    row["quality"] = q
                fields.append(row)
                off += len(data)
        manifest = self._manifest(step, fields, off, t0, extra=dict(layout="flat"))
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
        out = self._publish(tmp, final)
        dist.barrier(f"ckpt:{step}:{seq}:published", self.cfg.barrier_timeout_s)
        return out

    def _encoded_in_order(self, items: list, encode: Callable[[int], Any]):
        """Yield `encode(i)` in input order while a bounded thread pool runs
        ahead of the write cursor — only `2 * workers` results may sit
        encoded-but-unwritten, so byte streams can't pile up past RAM.
        Shared by the v1 and v2 writers so the window/drain logic cannot
        drift between the layouts."""
        cfg = self.cfg
        pool = (
            ThreadPoolExecutor(max_workers=cfg.workers)
            if cfg.workers > 1 and len(items) > 1
            else None
        )
        window = 2 * cfg.workers if pool else 1
        futs: deque = deque()
        nxt = 0
        try:
            for i in range(len(items)):
                if pool is not None:
                    while nxt < len(items) and len(futs) < window:
                        futs.append(pool.submit(encode, nxt))
                        nxt += 1
                    yield futs.popleft().result()
                else:
                    yield encode(i)
        finally:
            if pool is not None:
                pool.shutdown()

    def _manifest(self, step: int, fields: list, total_bytes: int, t0: float,
                  extra: dict | None = None) -> dict:
        """Manifest fields shared by both layouts (v3: `layout` comes in
        `extra`; `policy` records the configured Policy/PolicySet, and the
        legacy `mode`/`target` keys mirror the DEFAULT policy so pre-v3
        tooling keeps reading something sensible)."""
        default = self.cfg.policy_set.default
        # legacy `target` mirror: every target mode (fixed_psnr / ratio /
        # the §7.4 metric modes) reports its policy target via
        # TARGET_FIELD; fixed_accuracy reports the bound, raw None
        tgt_attr = TARGET_FIELD.get(default.mode)
        man = dict(
            step=step,
            version=3,
            policy=policy_set_spec(self.cfg.policy_set),
            mode=default.mode,
            target=(
                getattr(default, tgt_attr) if tgt_attr is not None
                else default.eb_rel if default.eb_rel is not None
                else default.eb_abs
            ),
            fields=fields,
            total_bytes=total_bytes,
            raw_bytes=int(
                sum(
                    int(np.prod(fl["shape"] or [1])) * np.dtype(fl["dtype"]).itemsize
                    for fl in fields
                )
            ),
            wall_time=time.time(),
            save_seconds=time.time() - t0,
            selection_bits={fl["name"]: fl["codec"] for fl in fields},
        )
        if extra:
            man.update(extra)
        if self.cache is not None:
            # persist the warm-save state (DESIGN.md §8.4): a restored run
            # reloads these entries and its first save revalidates them
            man["decision_cache"] = self.cache.to_manifest()
        return man

    def _publish(self, tmp: str, final: str) -> str:
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic publish
        with open(os.path.join(self.cfg.directory, ".LATEST_tmp"), "w") as f:
            f.write(os.path.basename(final))
        os.replace(
            os.path.join(self.cfg.directory, ".LATEST_tmp"),
            os.path.join(self.cfg.directory, "LATEST"),
        )
        self._prune()
        return final

    def _save_sharded(self, step: int, tree: Any, lossy: Callable[[str], bool]) -> str:
        """The segment-layout writer: shard-local decisions
        (`core/sharded.plan_tree`, one launch per policy group), per-shard
        segment encoding on the thread pool, per-host data files.
        Policy-raw and non-float leaves write exact original-dtype bytes,
        also per shard (codec ``none``) — nothing in this path gathers a
        tensor that the engine's layout analysis can keep sharded."""
        t0 = time.time()
        items, pol_of, plan_of = self._plan_sharded(tree, lossy)
        # only the write phase retries: `_plan_sharded` holds the device
        # collectives, which must not re-issue out of program order
        return self._retry_barrier_timeout(
            lambda: self._write_sharded(step, t0, items, pol_of, plan_of)
        )

    def _plan_sharded(self, tree: Any, lossy: Callable[[str], bool]):
        """Stage I/II for the segment writer: resolve policies and run the
        shard-local decision launches (`plan_tree`, one per policy group).
        Contains every COLLECTIVE of the save — psum reconciliation,
        moments fingerprints, fallback gathers — so in a multi-process job
        it must run on the main thread, in program order, on every host;
        `_write_sharded` (pure host IO + KV barriers) is then free to run
        on the async writer thread (DESIGN.md §6.2)."""
        from repro.core import sharded as shd

        items = _leaf_items_raw(tree)
        pol_of = self._resolve_policies(items, lossy)
        plan_of: dict[int, Any] = {}
        for pol, idxs in group_by_policy(pol_of).items():
            names = [items[i][0] for i in idxs] if self.cache is not None else None
            plans = shd.plan_tree(
                [items[i][1] for i in idxs], pol, cache=self.cache, names=names
            )
            plan_of.update(zip(idxs, plans))
        return items, pol_of, plan_of

    def _write_sharded(
        self, step: int, t0: float, items: list, pol_of: dict, plan_of: dict
    ) -> str:
        """Step 4 + publication, per host (DESIGN.md §6.2):

        1. every host encodes the segments it OWNS (`dist.owner_host` —
           replicated shards get exactly one writer, gather-fallback and
           host-array fields write on host 0) into `data.<host>.bin`;
        2. it records its rows in `segtable.<host>.json` (multi-host) and
           fsyncs, then writes the `commit.<host>` completion marker LAST;
        3. a bounded barrier fences the write phase — a dead/straggling
           host raises `BarrierTimeout` on every live host, the tmp dir is
           abandoned, nothing is ever promoted;
        4. host 0 merges the per-host segment tables into the manifest
           (recording `hosts` + per-host `completion` byte counts) and
           atomically promotes; a final bounded barrier makes every host
           return only after the step is visible (or raise if host 0
           died before publishing)."""
        from repro.core import sharded as shd
        from repro.runtime import sharding as rsh

        cfg = self.cfg
        host, nproc = dist.process_index(), dist.process_count()
        seq = self._save_seq
        self._save_seq += 1
        # multi-host tmp dirs must agree across processes (shared FS);
        # single-process keeps the pid suffix so concurrent managers in
        # tests cannot collide
        tag = "shared" if nproc > 1 else str(os.getpid())
        tmp = os.path.join(cfg.directory, f".tmp_step_{step:09d}_{tag}")
        final = os.path.join(cfg.directory, f"step_{step:09d}")
        os.makedirs(tmp, exist_ok=True)
        only = host if nproc > 1 else None

        def _encode(i: int):
            """-> (view_shape, sel_codec, eb, eb_sz, [(start, stop, codec, bytes)])

            `sel_codec` is the DECISION bit; the recorded field codec (the
            raw demote over every segment) is evaluated at manifest
            assembly, where all hosts' rows are visible."""
            name, leaf = items[i]
            plan = plan_of.get(i)
            if plan is not None:
                encoded = shd.encode_plan(
                    leaf, plan, host=only,
                    device_encode=self.cfg.device_encode,
                )
                segs = [(s.start, s.stop, s.codec, s.data) for s in encoded]
                sel = plan.selection
                return plan.view_shape, sel.codec, sel.eb_abs, sel.eb_sz, segs
            shape = tuple(int(s) for s in np.shape(leaf))
            if rsh.mesh_of(leaf) is not None and np.ndim(leaf) > 0:
                segs = [
                    (start, stop, "none",
                     rsh.shard_data(leaf, shd._local_device(devs)).tobytes())
                    for start, stop, devs in rsh.unique_shards(leaf)
                    if only is None or dist.owner_host(devs) == only
                ]
            elif only is not None and only != 0:
                segs = []  # host arrays are identical everywhere: host 0 writes
            else:
                arr = np.asarray(leaf)
                segs = [((0,) * arr.ndim, shape, "none", arr.tobytes())]
            return shape, "none", 0.0, 0.0, segs

        fields = []
        with open(os.path.join(tmp, f"data.{host}.bin"), "wb") as f:
            off = 0
            for i, ((name, leaf), (view_shape, sel_codec, eb, eb_sz, segs)) in enumerate(
                zip(items, self._encoded_in_order(items, _encode))
            ):
                seg_rows = []
                for start, stop, seg_codec, data in segs:
                    f.write(data)
                    seg_rows.append(
                        dict(
                            start=list(start), stop=list(stop),
                            codec=seg_codec, host=host,
                            offset=off, nbytes=len(data),
                        )
                    )
                    off += len(data)
                row = dict(
                    name=name, sel_codec=sel_codec,
                    shape=list(np.shape(leaf)), dtype=str(leaf.dtype),
                    view_shape=list(view_shape), eb=eb, eb_sz=eb_sz,
                    segments=seg_rows,
                    policy=_field_policy_spec(pol_of.get(i)),
                )
                plan = plan_of.get(i)
                q = _quality_record(plan.solution if plan is not None else None)
                if q is not None:
                    row["quality"] = q
                fields.append(row)
            if nproc > 1:
                f.flush()
                os.fsync(f.fileno())
        if nproc > 1:
            with open(os.path.join(tmp, f"segtable.{host}.json"), "w") as f:
                json.dump([fl["segments"] for fl in fields], f)
                f.flush()
                os.fsync(f.fileno())
        # the completion marker comes LAST: its existence certifies this
        # host's data + segment table are durably on disk (fsync only
        # matters multi-host — single-host's commit point stays the
        # atomic directory rename, and the sync would be pure latency)
        marker = os.path.join(tmp, f"commit.{host}")
        with open(marker + ".tmp", "w") as f:
            json.dump({"nbytes": off, "fields": len(fields)}, f)
            if nproc > 1:
                f.flush()
                os.fsync(f.fileno())
        os.replace(marker + ".tmp", marker)
        dist.barrier(f"ckpt:{step}:{seq}:written", cfg.barrier_timeout_s)
        if host == 0:
            self._assemble_and_publish(step, t0, tmp, final, fields, nproc)
        dist.barrier(f"ckpt:{step}:{seq}:published", cfg.barrier_timeout_s)
        return final

    def _assemble_and_publish(
        self, step: int, t0: float, tmp: str, final: str, fields: list, nproc: int
    ) -> None:
        """Host 0's manifest assembly: verify every host's completion
        marker, merge the per-host segment tables (decision metadata is
        replicated — psum reconciliation makes it identical on every host,
        so host 0's copies are authoritative), evaluate the per-field raw
        demote over the MERGED rows, and atomically promote."""
        from repro.core import sharded as shd

        completion: dict[str, int] = {}
        for h in range(nproc):
            marker = os.path.join(tmp, f"commit.{h}")
            if not os.path.exists(marker):  # pragma: no cover - barrier fences this
                raise IncompleteCheckpointError(
                    f"host {h} passed the write barrier without a completion "
                    f"marker ({marker})"
                )
            with open(marker) as f:
                completion[str(h)] = int(json.load(f)["nbytes"])
            if h > 0:
                with open(os.path.join(tmp, f"segtable.{h}.json")) as f:
                    for fl, rows in zip(fields, json.load(f)):
                        fl["segments"].extend(rows)
        total = 0
        for fl in fields:
            fl["segments"].sort(key=lambda r: (tuple(r["start"]), r["host"]))
            fl["nbytes"] = sum(r["nbytes"] for r in fl["segments"])
            fl["codec"] = shd.field_codec(
                fl.pop("sel_codec"), [r["codec"] for r in fl["segments"]]
            )
            total += fl["nbytes"]
        manifest = self._manifest(
            step, fields, total, t0,
            extra=dict(
                layout="segments", hosts=list(range(nproc)), completion=completion
            ),
        )
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=1)
            if nproc > 1:
                f.flush()
                os.fsync(f.fileno())
        self._publish(tmp, final)

    def async_save(self, step: int, tree: Any, **kw) -> threading.Thread:
        """Snapshot now; serialize+write on a worker thread. Unsharded saves
        snapshot to host memory; sharded saves snapshot DEVICE-side
        (a sharding-preserving jitted copy) so a training step that donates
        or overwrites its buffers cannot race the background writer — the
        copy costs transient HBM, not a gather. Any exception the worker
        hits — encoder failures included — is re-raised by `wait()`.

        The sharded save is PIPELINED (DESIGN.md §6.2): stats→solve (every
        device collective, `_plan_sharded`) runs here on the calling
        thread before the method returns — multi-host jobs must issue
        collectives in program order on the main thread — while
        encode→drain→barrier→publish (`_write_sharded`: host IO plus
        KV-service fences, all thread-safe) overlaps with step N+1 on the
        worker. A transiently straggling host is requeued up to
        `cfg.save_retries` times under fresh barrier keys; a persistent
        one surfaces as `BarrierTimeout` from `wait()`, never as a hang.
        On success the returned thread carries
        ``thread.save_result = {"path", "retries"}``."""
        self.wait()
        self._exc = None
        lossy = kw.pop("lossy", None)
        if kw:
            raise TypeError(f"async_save: unexpected kwargs {sorted(kw)}")
        if lossy is None:
            lossy = self._default_lossy()
        if self.cfg.sharded:
            snap = jax.tree_util.tree_map(
                lambda x: dist.device_copy(x) if isinstance(x, jax.Array)
                else np.array(x),
                tree,
            )
            t0 = time.time()
            items, pol_of, plan_of = self._plan_sharded(snap, lossy)
            # gather-fallback fields fetch at encode time — a collective
            # when the array spans processes — so materialize them on the
            # calling thread; the worker then never touches devices it
            # cannot address
            items = [
                (name, dist.to_numpy(leaf))
                if i in plan_of and not plan_of[i].sharded
                and isinstance(leaf, jax.Array)
                else (name, leaf)
                for i, (name, leaf) in enumerate(items)
            ]
            run = lambda: self._retry_barrier_timeout(  # noqa: E731
                lambda: self._write_sharded(step, t0, items, pol_of, plan_of)
            )
        else:
            # flat snapshot: `dist.to_numpy` is itself a collective for
            # leaves this process cannot fully address — calling thread too
            host_tree = jax.tree_util.tree_map(dist.to_numpy, tree)
            run = lambda: self.save(step, host_tree, lossy=lossy)  # noqa: E731

        def _run() -> None:
            try:
                path = run()
                # surfaced on the returned thread object: the async
                # caller's view of where the save landed and how many
                # BarrierTimeout requeues it needed (§6.2)
                thread.save_result = dict(
                    path=path, retries=self.last_save_retries
                )
            except BaseException as e:  # noqa: BLE001 - surfaced by wait()
                self._exc = e

        thread = threading.Thread(target=_run, daemon=True)
        thread.save_result = None
        self._thread = thread
        thread.start()
        return thread

    def wait(self) -> None:
        """Join the async save, re-raising whatever it raised: a failed
        checkpoint must fail loudly, not leave a stale LATEST behind."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        exc, self._exc = getattr(self, "_exc", None), None
        if exc is not None:
            raise exc

    def _prune(self) -> None:
        steps = sorted(
            d for d in os.listdir(self.cfg.directory) if d.startswith("step_")
        )
        for d in steps[: -self.cfg.keep_n]:
            shutil.rmtree(os.path.join(self.cfg.directory, d), ignore_errors=True)
        if not steps:
            return
        # GC torn writes: a crash between staging and promotion leaves a
        # `.tmp_step_*` dir behind forever. Any tmp older than the newest
        # COMMITTED step can never be promoted (promotion is monotone), so
        # it is garbage; a tmp at/above the newest step may be a save in
        # flight on another process and is left alone.
        newest = int(steps[-1].split("_")[1])
        for d in os.listdir(self.cfg.directory):
            if not d.startswith(".tmp_step_"):
                continue
            try:
                tmp_step = int(d.split("_")[2])
            except (IndexError, ValueError):
                continue
            if tmp_step < newest:
                shutil.rmtree(
                    os.path.join(self.cfg.directory, d), ignore_errors=True
                )

    # -- restore ------------------------------------------------------------

    def latest_step(self) -> int | None:
        p = os.path.join(self.cfg.directory, "LATEST")
        if not os.path.exists(p):
            return None
        with open(p) as f:
            return int(f.read().strip().split("_")[-1])

    def _resolve_step_dir(self, step: int | None) -> tuple[int, str]:
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoint in {self.cfg.directory}")
        return step, os.path.join(self.cfg.directory, f"step_{step:09d}")

    def _load_manifest(self, d: str) -> tuple[dict, str]:
        """Read + vet a step's manifest -> (manifest, layout).

        Layout dispatch: v3 records it explicitly; v2 is always the
        segment layout, v1 (no version key) always the flat one.
        Multi-host segment manifests — those carrying a `completion` key
        (DESIGN.md §6.2) — are validated against their per-host markers
        and data-file sizes: a checkpoint some host never finished must be
        REJECTED (`IncompleteCheckpointError`), not silently decoded
        short. Pre-completion manifests skip the check, so old
        checkpoints stay readable."""
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        if self.cache is not None and "decision_cache" in manifest:
            # resume warm: the next save revalidates these entries against
            # fresh fingerprints before trusting any of them (DESIGN.md §8)
            self.cache.load_manifest(manifest["decision_cache"])
        version = int(manifest.get("version", 1))
        layout = manifest.get("layout", "segments" if version == 2 else "flat")
        if layout == "segments" and "completion" in manifest:
            for h in manifest.get("hosts", []):
                if not os.path.exists(os.path.join(d, f"commit.{h}")):
                    raise IncompleteCheckpointError(
                        f"{d}: completion marker commit.{h} is missing — "
                        f"host {h}'s write never finished; refusing to decode"
                    )
                want = int(manifest["completion"].get(str(h), 0))
                data = os.path.join(d, f"data.{h}.bin")
                have = os.path.getsize(data) if os.path.exists(data) else -1
                if have < want:
                    raise IncompleteCheckpointError(
                        f"{d}: data.{h}.bin holds {have} bytes but the "
                        f"manifest records {want} — truncated write"
                    )
        return manifest, layout

    def restore(self, step: int | None = None) -> tuple[int, dict[str, np.ndarray]]:
        """Returns (step, {name: array}). Mesh-agnostic for BOTH layouts:
        the v1 single-file reader stays supported, and v2 per-shard
        segments reassemble into full tensors regardless of the saving
        mesh — the caller (or `restore_tree(shardings=...)`) reshards."""
        step, d = self._resolve_step_dir(step)
        manifest, layout = self._load_manifest(d)
        if layout == "segments":
            return step, self._restore_v2(d, manifest)
        with open(os.path.join(d, "data.bin"), "rb") as f:
            blob = f.read()

        def decode(fl: dict) -> np.ndarray:
            seg = blob[fl["offset"] : fl["offset"] + fl["nbytes"]]
            shape, dtype = tuple(fl["shape"]), np.dtype(fl["dtype"])
            if fl["codec"] == "none":
                # exact original-dtype bytes (non-float / policy-raw rows)
                return codecs.writeable_frombuffer(seg, dtype).reshape(shape)
            if fl["codec"] == "raw":
                # selection-era raw rows hold f32 working-dtype bytes
                return (
                    codecs.writeable_frombuffer(seg, np.float32)
                    .reshape(shape)
                    .astype(dtype)
                )
            cf = sel.CompressedField(fl["codec"], seg, shape, fl["dtype"])
            return sel.decompress(cf)

        # the host decoders spend their time in numpy, which releases the
        # GIL: decode on the save path's pool width, largest fields first
        fields = sorted(manifest["fields"], key=lambda fl: -fl["nbytes"])
        if self.cfg.workers > 1 and len(fields) > 1:
            with ThreadPoolExecutor(max_workers=self.cfg.workers) as ex:
                arrs = list(ex.map(decode, fields))
        else:
            arrs = [decode(fl) for fl in fields]
        by_name = {fl["name"]: arr for fl, arr in zip(fields, arrs)}
        return step, {fl["name"]: by_name[fl["name"]] for fl in manifest["fields"]}

    def _restore_v2(
        self, d: str, manifest: dict,
        need: dict[str, tuple[int, int]] | None = None,
    ) -> dict[str, np.ndarray]:
        """Elastic v2/v3 reader: paste each field's segments into its folded
        view (decompressing lossy ones), then reshape to the original
        shape/dtype. Works for any saving mesh — segments carry their own
        view coordinates, and each row's `host` key addresses the per-host
        data file it lives in (range reads via `_HostBlobs`: a file is
        opened only if a needed segment lives there).

        `need` (the multi-host `restore_tree` path) maps field name -> the
        conservative flat element span this process must materialize:
        only segments overlapping the span are read and decoded, the rest
        of the view buffer stays unfilled — IO and decode work scale with
        the LOCAL shard, not the global tensor. Fields with unfilled
        regions are only safe to consume shard-wise (`dist.put_global`
        slices exactly the addressable region), which is why the filter is
        reserved for that caller. `last_restore_stats` records the
        locality actually achieved."""
        from repro.core import sharded as shd

        blobs = _HostBlobs(d)
        n_total = n_decoded = 0
        out: dict[str, np.ndarray] = {}
        try:
            for fl in manifest["fields"]:
                shape, dtype = tuple(fl["shape"]), np.dtype(fl["dtype"])
                vshape = tuple(fl["view_shape"])
                rows = fl["segments"]
                n_total += len(rows)
                span = need.get(fl["name"]) if need is not None else None
                if span is not None:
                    rows = [
                        sg for sg in rows
                        if _spans_overlap(
                            span, _flat_span(sg["start"], sg["stop"], vshape)
                        )
                    ]
                n_decoded += len(rows)
                if fl["codec"] == "none":
                    arr = np.empty(vshape, dtype)  # writeable by construction
                    for sg in rows:
                        data = blobs.read(sg["host"], sg["offset"], sg["nbytes"])
                        ext = tuple(b - a for a, b in zip(sg["start"], sg["stop"]))
                        arr[
                            tuple(slice(a, b) for a, b in zip(sg["start"], sg["stop"]))
                        ] = np.frombuffer(data, dtype).reshape(ext)
                    out[fl["name"]] = arr.reshape(shape)
                    continue
                segments = [
                    shd.Segment(
                        tuple(sg["start"]), tuple(sg["stop"]), sg["codec"],
                        blobs.read(sg["host"], sg["offset"], sg["nbytes"]),
                    )
                    for sg in rows
                ]
                view = shd.decode_segments(vshape, segments)
                out[fl["name"]] = view.reshape(shape).astype(dtype)
            self.last_restore_stats = dict(
                segments_total=n_total,
                segments_decoded=n_decoded,
                hosts_opened=blobs.hosts_opened,
            )
        finally:
            blobs.close()
        return out

    def restore_tree(
        self, template: Any, step: int | None = None, shardings: Any = None
    ) -> tuple[int, Any]:
        """Restore into the structure of `template` (names must match).

        `shardings` (optional pytree of `jax.sharding.Sharding` matching
        `template`) re-shards every leaf onto a TARGET mesh as it loads —
        the elastic-restore path: a checkpoint saved at ANY mesh and host
        count resumes under any other (DESIGN.md §6). Leaves are placed
        with `dist.put_global`, so a target sharding spanning processes is
        built shard-by-shard — nothing is ever sent to a device this
        process cannot address. In a multi-process job, segment-layout
        restores additionally read + decode only the segments this
        process's addressable shards intersect (`last_restore_stats`
        reports the locality)."""
        step, d = self._resolve_step_dir(step)
        manifest, layout = self._load_manifest(d)
        leaves, treedef = jax.tree_util.tree_flatten_with_path(template)
        names = [
            "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
            for path, _ in leaves
        ]
        shard_list = (
            jax.tree_util.tree_structure(template).flatten_up_to(shardings)
            if shardings is not None
            else None
        )
        if layout == "segments":
            need = None
            if shard_list is not None and dist.is_multihost():
                need = {
                    name: _need_span(s, tuple(np.shape(leaf)))
                    for name, s, (_, leaf) in zip(names, shard_list, leaves)
                }
            flat = self._restore_v2(d, manifest, need=need)
        else:
            _, flat = self.restore(step)
        vals = []
        for name, (path, leaf) in zip(names, leaves):
            arr = flat[name]
            vals.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
        if shard_list is not None:
            vals = [dist.put_global(v, s) for v, s in zip(vals, shard_list)]
        tree = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template), vals
        )
        return step, tree
