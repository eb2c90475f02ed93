"""Algorithm 1 — automatic online selection between SZ and ZFP (paper §5.3).

Per field:
  1. sample blocks (rate r_sp);
  2. estimate ZFP's (BR, PSNR) at the user's error bound;
  3. invert Eq. (10) to get the SZ bin size delta matching ZFP's PSNR
     (iso-PSNR comparison -> rate-distortion-optimal choice);
  4. estimate SZ's BR at that delta;
  5. pick the compressor with the smaller estimated bit-rate.

Note (DESIGN.md §1): Algorithm 1 line 11 prints "error bound 2*delta"; the
derivation requires eb_sz = delta/2 (clamped to eb_abs so the user's bound
always holds). We implement the consistent reading.

The quality-target modes (fixed_psnr and the §7.4 metric targets) reuse
the same min-rate rule but anchor it at the caller's contract instead of
at matched eb: the controller solves each codec's bound onto the target
first, then the cheapest candidate *inside the target's tolerance band*
wins (`core/controller.py`). `select_many` therefore only accepts
fixed_accuracy policies and points target modes at `solve_many`.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache as _lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import codecs as _codecs
from . import estimator as est
from .policy import Policy

#: a codec *name*; byte encode/decode dispatches through the registry
#: (`core/codecs.py`, DESIGN.md §2.1), so the set is open, not a Literal
Codec = str


def _pick_codec(br_sz: float, br_zfp: float, allowed: tuple[str, ...]) -> Codec:
    """Step 5 of Fig. 2 under a codec allowlist: min estimated rate among
    the allowed lossy candidates, `raw` when the best still exceeds 32
    bits/value (or nothing lossy is allowed). With the full allowlist this
    is exactly the historical `"sz" if br_sz < br_zfp else "zfp"` rule —
    ties keep going to ZFP — so default-policy decisions are unchanged."""
    sz_ok, zfp_ok = "sz" in allowed, "zfp" in allowed
    if sz_ok and zfp_ok:
        codec, best = ("sz", br_sz) if br_sz < br_zfp else ("zfp", br_zfp)
    elif sz_ok:
        codec, best = "sz", br_sz
    elif zfp_ok:
        codec, best = "zfp", br_zfp
    else:
        return "raw"
    return "raw" if best >= 32.0 else codec


@dataclass
class Selection:
    codec: Codec
    eb_abs: float            # user bound (guaranteed pointwise)
    eb_sz: float             # SZ bound after the iso-PSNR match
    br_sz: float
    br_zfp: float
    psnr_target: float       # ZFP's estimated PSNR (the match point)
    vr: float
    r_sp: float


def select(
    x: jax.Array | np.ndarray,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    r_sp: float = est.DEFAULT_SAMPLING_RATE,
    transform: str = "zfp",
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> Selection:
    """Run Steps 1-3 of Fig. 2 and return the decision + estimates."""
    x = _fold_ndim(jnp.asarray(x))
    vr = float(jnp.max(x) - jnp.min(x)) if x.size else 0.0
    sel0 = _degenerate_selection(x, vr, eb_abs, eb_rel, r_sp)
    if sel0 is not None:
        return sel0
    if eb_abs is None:
        assert eb_rel is not None, "need eb_abs or eb_rel"
        eb_abs = eb_rel * vr
    starts = est.block_starts(x.shape, r_sp)
    br_sz, br_zfp, psnr_zfp, eb_sz = _estimates_jitted(
        x.shape, starts.shape, transform
    )(x, jnp.asarray(starts), jnp.float32(eb_abs), jnp.float32(vr))
    br_sz, br_zfp = float(br_sz), float(br_zfp)
    eb_sz = float(eb_sz)
    codec = _pick_codec(br_sz, br_zfp, codecs)
    return Selection(codec, float(eb_abs), eb_sz, br_sz, br_zfp, float(psnr_zfp), vr, r_sp)


# ---------------------------------------------------------------------------
# Batched multi-field selection (the engine behind compress_pytree and the
# checkpoint writer; DESIGN.md §1, §4–§5)
# ---------------------------------------------------------------------------


def _fold_ndim(x):
    """Fields are 1-3D; fold leading axes of higher-rank tensors, and merge
    leading axes shorter than the 4-wide block (e.g. a (2, 128, 128)
    stacked-layer tensor becomes (256, 128) instead of falling back to raw).
    Shared by `select`, `select_many`, and `encode_with_selection` so the
    decision and the encoded view always agree."""
    if x.ndim > 3:
        x = x.reshape((-1,) + x.shape[-2:])
    while x.ndim > 1 and x.shape[0] < 4 and x.size:
        x = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
    return x


def _degenerate_selection(x, vr: float, eb_abs, eb_rel, r_sp: float) -> Selection | None:
    """The raw-fallback policy, shared by `select` and `select_many` so the
    two paths cannot drift: too-small fields, constant fields, and
    NaN/inf-poisoned fields (vr non-finite) all store verbatim. `vr` is
    computed by the caller (device-side for `select`, host-side for
    `select_many`); pass 0.0 for empty fields."""
    if x.ndim == 0 or (x.size and min(x.shape) < 4) or x.size < 64:
        eb = eb_abs if eb_abs is not None else (eb_rel or 1e-3) * max(vr, 1e-30)
        return Selection("raw", float(eb), float(eb), 32.0, 32.0, 0.0, vr, r_sp)
    if vr <= 0 or not np.isfinite(vr):
        eb = eb_abs if eb_abs is not None else 1e-30
        return Selection("raw", float(eb), float(eb), 32.0, 32.0, 0.0, vr, r_sp)
    return None


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


@_lru_cache(maxsize=64)
def _batched_estimates_jitted(nd: int, n_blocks: int, n_fields: int, transform: str):
    """Jitted Steps 1-3 of Fig. 2 over a packed multi-field block batch.

    Cached per (ndim, padded block count, padded field count) — both counts
    are padded to power-of-two buckets by `select_many`, so a checkpoint
    with hundreds of distinctly-shaped tensors compiles O(log) programs,
    not O(fields).
    """

    def select_estimate_batched(halo, seg, bounds, eb_f, vr_f, size_f):
        # the no-halo blocks are the halo blocks minus the leading
        # original-neighbor row on each axis (the boundary mask only ever
        # zeroes those -1 offsets), so one gather serves both estimators
        nohalo = halo[(slice(None),) + (slice(1, None),) * nd]
        e_zfp = est.estimate_zfp_many(nohalo, seg, bounds, eb_f, vr_f, transform)
        delta = est.sz_delta_for_psnr(e_zfp.psnr, vr_f)
        eb_sz = jnp.clip(delta / 2.0, eb_f * 1e-6, eb_f)
        e_sz = est.estimate_sz_many(halo, seg, bounds, 2.0 * eb_sz, vr_f, size_f)
        return e_sz.bitrate, e_zfp.bitrate, e_zfp.psnr, eb_sz

    return jax.jit(select_estimate_batched)


#: per-launch field cap. Two constraints, the second binding: (a) the
#: batched SZ estimator's int32 sort key seg * (n_pdf + 1) + bin must stay
#: below 2^31 after pow2 field padding (would allow ~32k); (b) the per-run
#: |p log2 p| entropy terms ride an f32 prefix sum whose running total
#: grows ~17 bits/field, so the cap keeps the late-field window error
#: around 1e-3 bits/value — far below any real decision margin (f64
#: accumulation is unavailable without jax x64 mode).
MAX_BATCH_FIELDS = 1024


def _max_batch_blocks(nd: int) -> int:
    """Per-launch block cap: bounds batch memory AND keeps the int32
    coder-bit prefix sums in `field_sums` exact — the coder's worst case
    is ~31 planes x (2 significance/refinement bits per coefficient + the
    k field) + header, < 4^nd * 128 bits per block, so
    cap * 4^nd * 128 < 2^31. Larger pytrees simply run a few launches; a
    single field bigger than the cap falls back to the per-field `select`
    path."""
    return min(1 << 20, (1 << 31) // (4**nd * 128))


def select_many(
    fields,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    r_sp: float | None = None,
    transform: str = "zfp",
    codecs: tuple[str, ...] | None = None,
    *,
    policy: Policy | None = None,
    cache=None,
    names=None,
) -> list[Selection]:
    """Algorithm 1 on MANY fields with one estimator launch (per ndim group).

    Sampled blocks of every field are gathered on host (r_sp of the bytes),
    packed into one padded (total_blocks, 4, ..) batch per dimensionality,
    and Steps 1-3 run as a single jitted call with per-field segment
    reductions — one compile + one device round-trip per pytree instead of
    one per leaf. Returns one `Selection` per input field, matching the
    per-field `select` decision.

    `policy` (a fixed_accuracy `Policy`) is the object form of the
    eb/r_sp/codecs arguments — the bound-centric quality contract of
    DESIGN.md §2 — and is what `compress_pytree` passes per policy group;
    the explicit kwargs remain the primitive, non-deprecated spelling for
    direct Algorithm-1 use. `codecs` restricts which registered codecs
    (DESIGN.md §2.1) may compete; the full default reproduces the paper's
    SZ-vs-ZFP rule exactly.

    Fields are evaluated in float32 (the codecs' working dtype); the f32
    view of each field is transient — only its sampled blocks are retained,
    so peak memory is one field plus ~r_sp of the pytree.

    `cache` (a `DecisionCache`, DESIGN.md §8) with `names` (one stable
    field path per field) enables the warm path: each batchable member's
    sampled blocks are fingerprinted (`core/predictor.py`), validated
    entries replay the previous save's `Selection` verbatim — bit-identical
    to what the cold path would recompute, since the fingerprint digests
    the decision's complete preimage — and only misses run the estimator
    launch. Degenerate fields (tiny/constant/NaN-poisoned) never consult
    the cache; their raw fallback is re-derived every call.
    """
    if policy is not None:
        if policy.mode != "fixed_accuracy":
            raise ValueError(
                f"select_many takes a fixed_accuracy policy, got {policy.mode!r} "
                "(use controller.solve_many for the target modes: fixed_psnr, "
                "fixed_ratio, fixed_ssim, fixed_correlation, fixed_ks)"
            )
        if any(v is not None for v in (eb_abs, eb_rel, r_sp, codecs)):
            raise ValueError(
                "pass either policy= or eb_abs/eb_rel/r_sp/codecs, not both"
            )
        eb_abs, eb_rel = policy.eb_abs, policy.eb_rel
        r_sp, codecs = policy.r_sp, policy.codecs
    r_sp = est.DEFAULT_SAMPLING_RATE if r_sp is None else r_sp
    codecs = _codecs.DEFAULT_CODECS if codecs is None else codecs
    fields = list(fields)
    results: list[Selection | None] = [None] * len(fields)
    groups = _build_select_members(
        fields, range(len(fields)), results, eb_abs, eb_rel, r_sp, transform,
        codecs,
    )
    if cache is None:
        _run_select_batches(groups, results, r_sp, transform, codecs)
        return results  # type: ignore[return-value]
    if policy is None:
        policy = Policy.fixed_accuracy(
            eb_rel=eb_rel, eb_abs=eb_abs, r_sp=r_sp, codecs=codecs
        )
    _select_many_cached(
        fields, names, results, groups, cache, policy, r_sp, transform, codecs
    )
    return results  # type: ignore[return-value]


def _select_many_cached(
    fields,
    names,
    results: list[Selection | None],
    groups,
    cache,
    policy: Policy,
    r_sp: float,
    transform: str,
    codecs: tuple[str, ...],
) -> None:
    """Warm half of `select_many` (DESIGN.md §8): fingerprint each
    batchable member, replay validated cache entries, batch only the
    misses through the ordinary estimator launch, store fresh decisions.

    Note the batch-composition caveat: a re-decided miss subset is batched
    with the OTHER misses of the same call, not with the hit fields — so a
    miss's decision is bit-identical to a cold `select_many` over the same
    miss subset (the f32 prefix-sum window differs at ulp level across
    batch compositions; see `estimator.field_sums`). Hits, by contrast,
    replay the stored decision exactly as originally batched."""
    from . import predictor as _pred

    if names is None:
        raise ValueError("select_many(cache=...) requires names=")
    names = list(names)
    if len(names) != len(fields):
        raise ValueError(
            f"names/fields length mismatch: {len(names)} vs {len(fields)}"
        )
    miss_groups: dict[int, list] = {}
    to_store: list[tuple[int, str, tuple, str, dict]] = []
    for nd, members in groups.items():
        stats = _pred.stats_for_members(nd, members, r_sp)
        for m, (_stats, fp) in zip(members, stats):
            i = m[0]
            x = fields[i]
            shape = tuple(np.shape(x))
            dtype = str(getattr(x, "dtype", np.asarray(x).dtype))
            entry = cache.lookup(names[i], shape, dtype, policy, transform, fp)
            if entry is not None:
                results[i] = entry.to_selection()
            else:
                miss_groups.setdefault(nd, []).append(m)
                to_store.append((i, names[i], shape, dtype, fp))
    if miss_groups:
        _run_select_batches(miss_groups, results, r_sp, transform, codecs)
    for i, name, shape, dtype, fp in to_store:
        cache.store(name, shape, dtype, policy, transform, fp, results[i])


def _build_select_members(
    fields,
    indices,
    results: list[Selection | None],
    eb_abs: float | None,
    eb_rel: float | None,
    r_sp: float,
    transform: str,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> dict[int, list[tuple[int, np.ndarray, float, float, int]]]:
    """Gather-side half of `select_many`: fold + value range + degenerate
    short-circuit + monster-field per-field fallback (written straight into
    `results` at the given indices), returning the batchable members as
    nd -> [(result index, halo blocks, eb, vr, size)] — the no-halo blocks
    are recovered in-graph by slicing off the leading halo row per axis.

    Split out so the shard-local engine (DESIGN.md §6) can merge its
    device-gathered members with host-gathered ones INTO THE SAME BATCHES:
    batch composition then matches the unsharded call exactly, which is
    what makes mixed eligible/fallback pytrees decide bit-identically."""
    groups: dict[int, list[tuple[int, np.ndarray, float, float, int]]] = {}
    span = TraceAnnotation("repro.compress.gather", fields=len(fields))
    with span:
        for i, x in zip(indices, fields):
            arr = np.asarray(x, dtype=np.float32)
            view = _fold_ndim(arr)
            vr = float(np.max(view) - np.min(view)) if view.size else 0.0
            sel0 = _degenerate_selection(view, vr, eb_abs, eb_rel, r_sp)
            if sel0 is not None:
                results[i] = sel0
                continue
            if eb_abs is None:
                assert eb_rel is not None, "need eb_abs or eb_rel"
                eb = eb_rel * vr
            else:
                eb = eb_abs
            starts = est.block_starts(view.shape, r_sp)
            if len(starts) > _max_batch_blocks(view.ndim):
                # monster field: bigger alone than a whole batch — the
                # per-field path has no int32 accumulation to protect
                with TraceAnnotation("repro.fallback.per_field_select"):
                    results[i] = select(
                        view, eb_abs=float(eb), r_sp=r_sp, transform=transform,
                        codecs=codecs,
                    )
                continue
            groups.setdefault(view.ndim, []).append((
                i,
                est.gather_blocks_np(view, starts, halo=True),
                float(eb), vr, view.size,
            ))
        span.set_metadata(blocks=sum(len(m[1]) for ms in groups.values() for m in ms))
    return groups


def _run_select_batches(
    groups: dict[int, list[tuple[int, np.ndarray, float, float, int]]],
    results: list[Selection | None],
    r_sp: float,
    transform: str,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> None:
    """Drive `_select_batch` over pre-gathered members, honoring the per-ndim
    block cap and field cap. Members are (input index, halo blocks, eb, vr,
    size) tuples; shared by `select_many` (host-gathered samples) and the
    shard-local engine (device-gathered samples, DESIGN.md §6) so the two
    paths run the identical decision program on identical inputs."""
    for nd, members in groups.items():
        cap = _max_batch_blocks(nd)
        lo = 0
        while lo < len(members):
            hi, blocks = lo, 0
            while hi < len(members) and (
                hi == lo
                or (blocks + len(members[hi][1]) <= cap and hi - lo < MAX_BATCH_FIELDS)
            ):
                blocks += len(members[hi][1])
                hi += 1
            # the launch with its input packing: concatenating the samples
            # into the padded batch costs about as much as the launch itself
            with TraceAnnotation(
                "repro.compress.estimate", fields=hi - lo, n_blocks=_next_pow2(blocks)
            ):
                _select_batch(nd, members[lo:hi], results, r_sp, transform, codecs)
            lo = hi


def _select_batch(
    nd: int,
    members: list[tuple[int, np.ndarray, float, float, int]],
    results: list[Selection | None],
    r_sp: float,
    transform: str,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> None:
    halo = np.concatenate([m[1] for m in members], axis=0)
    seg = np.concatenate(
        [np.full(len(m[1]), f, dtype=np.int32) for f, m in enumerate(members)]
    )
    eb_l = [m[2] for m in members]
    vr_l = [m[3] for m in members]
    size_l = [m[4] for m in members]
    n_real_blocks, n_real_fields = len(seg), len(members)
    # pad to power-of-two buckets; padding blocks point at a dummy field slot
    n_blocks = _next_pow2(n_real_blocks)
    n_fields = _next_pow2(n_real_fields + 1)
    pad = n_blocks - n_real_blocks
    if pad:
        halo = np.concatenate([halo, np.zeros((pad,) + halo.shape[1:], np.float32)])
        seg = np.concatenate([seg, np.full(pad, n_fields - 1, np.int32)])
    # field boundary array: blocks of field f live at [bounds[f], bounds[f+1]);
    # empty padded slots collapse, the last slot absorbs the padding blocks
    bounds = np.zeros(n_fields + 1, np.int32)
    bounds[1 : n_real_fields + 1] = np.cumsum([len(m[1]) for m in members])
    bounds[n_real_fields + 1 :] = n_real_blocks
    bounds[n_fields] = n_blocks
    def padf(v, fill):
        return np.asarray(v + [fill] * (n_fields - n_real_fields), np.float32)

    fn = _batched_estimates_jitted(nd, n_blocks, n_fields, transform)
    br_sz, br_zfp, psnr, eb_sz = fn(
        jnp.asarray(halo), jnp.asarray(seg),
        jnp.asarray(bounds), jnp.asarray(padf(eb_l, 1.0)),
        jnp.asarray(padf(vr_l, 1.0)), jnp.asarray(padf(size_l, 1.0)),
    )
    br_sz, br_zfp = np.asarray(br_sz), np.asarray(br_zfp)
    psnr, eb_sz = np.asarray(psnr), np.asarray(eb_sz)
    for f, (i, _, eb, vr, _) in enumerate(members):
        bs, bz = float(br_sz[f]), float(br_zfp[f])
        codec = _pick_codec(bs, bz, codecs)
        results[i] = Selection(
            codec, float(eb), float(eb_sz[f]), bs, bz, float(psnr[f]), vr, r_sp
        )


@_lru_cache(maxsize=256)
def _estimates_jitted(x_shape, starts_shape, transform: str):
    """Jitted Steps 1-3 of Fig. 2, cached per (field shape, sample grid).

    Compiles once per field shape — the in-situ setting compresses the same
    fields every timestep, so the paper's <7% overhead target is met after
    the first field (see bench_overhead).
    """

    def select_estimate(x, starts, eb_abs, vr):
        e_zfp = est.estimate_zfp(x, eb_abs, starts, vr, transform)
        delta = est.sz_delta_for_psnr(e_zfp.psnr, vr)
        # clamp: degenerate (near-lossless) ZFP PSNR estimates would drive
        # the SZ bin size to 0 -> inf codes; floor keeps Algorithm 1 sane
        eb_sz = jnp.clip(delta / 2.0, eb_abs * 1e-6, eb_abs)
        e_sz = est.estimate_sz(x, 2.0 * eb_sz, starts, vr)
        return e_sz.bitrate, e_zfp.bitrate, e_zfp.psnr, eb_sz

    return jax.jit(select_estimate)


# ---------------------------------------------------------------------------
# Step 4 — construct the selected compressor and run it
# ---------------------------------------------------------------------------


@dataclass
class CompressedField:
    codec: Codec             # the selection bit s_i
    data: bytes
    shape: tuple[int, ...]
    dtype: str
    selection: Selection | None = None

    @property
    def nbytes(self) -> int:
        return len(self.data)


#: Stage III runs on the chip only for fields of at least this many values:
#: the smallest size at which the device tier beat the host coders for both
#: codecs, warm, in `tools/encode_tiers.py` on one v5e (PERF.md §7). At 2^18
#: values the host SZ coder still won (0.18 s against 0.19 s); a device
#: encode pays a launch and a fetch per pass, and a compile per pow2 arena
#: bucket.
DEVICE_ENCODE_MIN_VALUES = 1 << 20


def encode_tier(
    codec: Codec, n_values: int, device_encode: bool | None = None
) -> str:
    """Where one field's Stage III runs: "device" (the codec's in-graph
    encoder, capability `device_encode`, DESIGN.md §3.7) or "host".

    `device_encode=None` decides from what the caller can observe: the
    device tier runs when the arrays live on a TPU backend and the field
    holds at least `DEVICE_ENCODE_MIN_VALUES` values. True or False forces
    a path; codecs without the capability always encode on the host."""
    if not getattr(_codecs.get(codec), "device_encode", False):
        return "host"
    if device_encode is None:
        device_encode = (
            n_values >= DEVICE_ENCODE_MIN_VALUES and jax.default_backend() == "tpu"
        )
    return "device" if device_encode else "host"


def encode_view(view32: np.ndarray, sel: Selection, tier: str) -> bytes:
    """The codec's stream for one folded f32 view on `tier` (`encode_tier`).
    A device encoder that declines (None, the §3.7 fallback rules) hands
    the field to the host coder: never a truncated stream."""
    codec = _codecs.get(sel.codec)
    if tier == "device":
        data = codec.encode_device(view32, sel)
        if data is not None:
            return data
        with TraceAnnotation("repro.fallback.device_declined"):
            return codec.encode(view32, sel)
    return codec.encode(view32, sel)


def encode_with_selection(
    x: np.ndarray, sel: Selection, *, device_encode: bool | None = None
) -> CompressedField:
    """Step 4: run the already-selected compressor on `x`.

    Split from `select_and_compress` so batched callers (compress_pytree,
    the checkpoint writer) can make ALL decisions in one device call via
    `select_many` and then encode fields on a thread pool while the device
    is free for the next batch. The byte codec is resolved through the
    registry (DESIGN.md §2.1), so registered codecs beyond sz/zfp encode
    through the same path.

    `encode_tier` decides where Stage III runs (`device_encode=None`:
    from the backend and the field's size; True/False force a path). On
    the device tier the packed stream comes back in one `device_get` and
    decodes through the same registry decoder. Encoders return None under
    the §3.7 fallback rules, and the host coder then runs — same container
    either way, never a truncated stream.
    """
    x = np.asarray(x)
    orig_shape, orig_dtype = x.shape, x.dtype
    view = _fold_ndim(x.astype(np.float32))
    if view.ndim == 0:
        view = view.reshape(1)
    data = encode_view(view, sel, encode_tier(sel.codec, view.size, device_encode))
    # safety net: never ship a stream larger than raw
    if len(data) >= view.nbytes and sel.codec != "raw":
        with TraceAnnotation("repro.fallback.stream_not_smaller"):
            sel = Selection(
                "raw", sel.eb_abs, sel.eb_sz, 32.0, 32.0, sel.psnr_target, sel.vr, sel.r_sp
            )
            data = view.tobytes()
    return CompressedField(sel.codec, data, orig_shape, str(orig_dtype), sel)


def select_and_compress(
    x: np.ndarray,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    r_sp: float = est.DEFAULT_SAMPLING_RATE,
) -> CompressedField:
    x = np.asarray(x)
    sel = select(x.astype(np.float32), eb_abs=eb_abs, eb_rel=eb_rel, r_sp=r_sp)
    return encode_with_selection(x, sel)


def decompress(cf: CompressedField) -> np.ndarray:
    """Invert any `CompressedField`, lossy or raw, to a writeable array.

    Two raw conventions coexist and `selection` disambiguates: fields that
    went through a `Selection` (including lossy-decided/safety-net raw)
    hold f32 working-dtype bytes; selection-less raw fields — `Policy.raw`
    leaves, non-float leaves — hold exact ORIGINAL-dtype bytes, restored
    bit-identically (f64 precision, int payloads, and all)."""
    if cf.codec == "raw" and cf.selection is None:
        return _codecs.writeable_frombuffer(cf.data, cf.dtype).reshape(cf.shape)
    out = _codecs.get(cf.codec).decode(cf.data)
    return out.reshape(cf.shape).astype(cf.dtype)


def compression_ratio(cf: CompressedField) -> float:
    n = int(np.prod(cf.shape)) if cf.shape else 1
    return (n * 4) / max(len(cf.data), 1)
