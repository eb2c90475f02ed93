"""Statistical ratio/PSNR prediction + stats fingerprints (DESIGN.md §8).

"Black-Box Statistical Prediction of Lossy Compression Ratios for
Scientific Data" (Underwood et al. 2023, arXiv 2305.08801) shows that a
handful of cheap per-field statistics predict compression-ratio curves
well enough to skip sampled estimation for most fields. This module is
that idea fitted to our Algorithm-1 pipeline (paper §5.3):

* `stats_for_members` computes per-field **moments** — value range,
  sample min/max, Lorenzo-residual absolute/second/fourth moments
  (variance, kurtosis), a value-variance spectral-slope proxy, and a
  host-side residual IQR — over exactly the packed halo-block batch that
  `selector._select_batch` launches for Stage I (same padding buckets,
  same field-ordered prefix-sum reduction, `estimator.field_sums`), so
  the warm path adds one tiny jitted launch per (nd, bucket) and the
  cold path pays nothing.
* `predict_curves` turns those moments into predicted bitrate/PSNR
  curves for both codecs: SZ rides the Gaussian-entropy rate of the
  quantized residual (monotone non-increasing in the error bound by
  construction) with Eq. (11) PSNR; ZFP rides a significant-bit-plane
  model of the same residual scale. `predict_selection` then replays
  Algorithm 1 (iso-PSNR match, min-rate pick) on the predicted curves.
* every prediction carries a **confidence** in [0, 1] built from sample
  size, residual kurtosis (heavy tails break the entropy model), and the
  Laplacian-vs-Gaussian shape ratio; `select_many_predicted` routes
  fields below `CONFIDENCE_THRESHOLD` — and all degenerate fields — to
  the existing sampled estimator, keeping the quality contract exact
  where the model is least trustworthy (the arXiv 2310.14133 stance:
  cheapen the estimate, never the contract).
* `fingerprint_of` digests the sampled halo blocks + (vr, size, r_sp)
  into the content fingerprint `core/decision_cache.py` keys on: the
  digest covers the complete preimage of the batched Stage-I decision,
  which is what makes a validated cache hit bit-identical to cold.

Prediction is OPT-IN (`select_many_predicted`); the default
`select_many` path always runs the sampled estimator, so frozen goldens
and the paper-replication benches are untouched.
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass
from functools import lru_cache as _lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from . import codecs as _codecs
from . import estimator as est
from . import selector as _sel

#: predictions below this confidence route to the sampled estimator
CONFIDENCE_THRESHOLD = 0.5
#: fields with fewer sampled residuals than this never predict (the
#: moment estimates are too noisy to beat one cheap sampled launch)
MIN_CONFIDENT_SIZE = 4096
#: ZFP's measured truncation error sits WELL below the bound `eb` (the
#: kept bit-planes quantize most coefficients much finer than the last
#: one): PSNR_sp lands 23-34 dB above the naive -20*log10(eb/vr) across
#: the bench suites. The center of that band, calibrated against
#: `estimate_zfp(mode='exact')`; the +-6 dB spread costs the iso-PSNR
#: match about one bit of predicted SZ rate.
ZFP_PSNR_OFFSET = 28.0
#: residual kurtosis above the Gaussian/Laplacian band (3..6) decays
#: confidence with this scale — heavy tails break the entropy model
KURTOSIS_SCALE = 10.0
#: fingerprint format tag; bump on any change to the digest preimage
_FP_TAG = b"repro-dc1"

_LOG2_2PIE = math.log2(2.0 * math.pi * math.e)


@dataclass
class FieldStats:
    """Cheap per-field sufficient statistics (moments normalized by vr)."""

    vr: float          # value range (max - min of the folded f32 view)
    size: int          # folded element count
    n_blocks: int      # sampled blocks backing the moments
    smin: float        # sampled min / max (vr-normalized to [0, 1] span)
    smax: float
    ra1: float         # mean |residual| / vr
    rv2: float         # mean residual^2 / vr^2 (residual variance proxy)
    rk4: float         # mean residual^4 / vr^4
    vv2: float         # value variance / vr^2 (spectral-slope proxy:
                       # rv2/vv2 is the high-frequency energy fraction)
    iqr: float         # residual interquartile range / vr (host-side)
    nd: int
    r_sp: float

    @property
    def kurtosis(self) -> float:
        return self.rk4 / max(self.rv2 * self.rv2, 1e-38)


# ---------------------------------------------------------------------------
# Packed moments launch — same batch layout as selector._select_batch
# ---------------------------------------------------------------------------


@_lru_cache(maxsize=64)
def _moments_jitted(nd: int, n_blocks: int, n_fields: int):
    """Per-field moment reduction over a packed halo-block batch.

    Mirrors `_batched_estimates_jitted`'s cache discipline: one compile
    per (ndim, padded block bucket, padded field bucket). The residual is
    the nd-fold backward difference of the halo block — the same
    first-order Lorenzo stencil Stage I samples — normalized per field by
    vr so the f32 prefix sums stay comparable across co-batched fields
    (the `field_sums` contract)."""

    def predictor_moments(halo, seg, bounds, vr_f):
        nohalo = halo[(slice(None),) + (slice(1, None),) * nd]
        d = halo
        for ax in range(1, nd + 1):
            d = jnp.diff(d, axis=ax)
        inv_vr = 1.0 / jnp.maximum(vr_f, 1e-30)
        dn = d.reshape(d.shape[0], -1) * inv_vr[seg][:, None]
        vn = nohalo.reshape(nohalo.shape[0], -1) * inv_vr[seg][:, None]
        cols = jnp.stack(
            [
                jnp.sum(jnp.abs(dn), axis=1),
                jnp.sum(dn * dn, axis=1),
                jnp.sum((dn * dn) * (dn * dn), axis=1),
                jnp.sum(vn, axis=1),
                jnp.sum(vn * vn, axis=1),
            ],
            axis=1,
        )
        sums = est.field_sums(cols, bounds)  # (n_fields, 5)
        bmin = jnp.min(nohalo.reshape(nohalo.shape[0], -1), axis=1)
        bmax = jnp.max(nohalo.reshape(nohalo.shape[0], -1), axis=1)
        fmin = jnp.full((n_fields,), jnp.inf, jnp.float32).at[seg].min(bmin)
        fmax = jnp.full((n_fields,), -jnp.inf, jnp.float32).at[seg].max(bmax)
        return sums, fmin, fmax

    return jax.jit(predictor_moments)


def fingerprint_of(
    halo: np.ndarray, vr: float, size: int, r_sp: float
) -> str:
    """Content digest over the complete preimage of the batched Stage-I
    decision for one field: the sampled halo blocks themselves plus the
    (vr, size, r_sp) scalars the estimators consume. Equal digests =>
    `_select_batch` is a pure function of equal inputs => equal decision."""
    h = hashlib.blake2b(digest_size=16)
    h.update(_FP_TAG)
    h.update(np.asarray(halo.shape, np.int64).tobytes())
    h.update(np.asarray([vr, float(size), r_sp], np.float64).tobytes())
    h.update(np.ascontiguousarray(halo, dtype=np.float32).tobytes())
    return h.hexdigest()


def stats_for_members(
    nd: int,
    members: list[tuple[int, np.ndarray, float, float, int]],
    r_sp: float,
) -> list[tuple[FieldStats, dict]]:
    """(FieldStats, fingerprint record) per member, in member order.

    `members` are `_build_select_members` tuples
    (result index, halo blocks, eb, vr, size); the launch is chunked by
    the same per-ndim block/field caps as `_run_select_batches`."""
    out: list[tuple[FieldStats, dict]] = []
    cap = _sel._max_batch_blocks(nd)
    lo = 0
    while lo < len(members):
        hi, blocks = lo, 0
        while hi < len(members) and (
            hi == lo
            or (
                blocks + len(members[hi][1]) <= cap
                and hi - lo < _sel.MAX_BATCH_FIELDS
            )
        ):
            blocks += len(members[hi][1])
            hi += 1
        out.extend(_stats_batch(nd, members[lo:hi], r_sp))
        lo = hi
    return out


def _stats_batch(nd, members, r_sp) -> list[tuple[FieldStats, dict]]:
    halo = np.concatenate([m[1] for m in members], axis=0)
    seg = np.concatenate(
        [np.full(len(m[1]), f, dtype=np.int32) for f, m in enumerate(members)]
    )
    n_real_blocks, n_real_fields = len(seg), len(members)
    n_blocks = _sel._next_pow2(n_real_blocks)
    n_fields = _sel._next_pow2(n_real_fields + 1)
    pad = n_blocks - n_real_blocks
    if pad:
        halo_p = np.concatenate(
            [halo, np.zeros((pad,) + halo.shape[1:], np.float32)]
        )
        seg_p = np.concatenate([seg, np.full(pad, n_fields - 1, np.int32)])
    else:
        halo_p, seg_p = halo, seg
    bounds = np.zeros(n_fields + 1, np.int32)
    bounds[1 : n_real_fields + 1] = np.cumsum([len(m[1]) for m in members])
    bounds[n_real_fields + 1 :] = n_real_blocks
    bounds[n_fields] = n_blocks
    vr_l = [m[3] for m in members] + [1.0] * (n_fields - n_real_fields)
    fn = _moments_jitted(nd, n_blocks, n_fields)
    sums, fmin, fmax = fn(
        jnp.asarray(halo_p), jnp.asarray(seg_p), jnp.asarray(bounds),
        jnp.asarray(vr_l, jnp.float32),
    )
    sums = np.asarray(sums)
    fmin, fmax = np.asarray(fmin), np.asarray(fmax)
    nblk_f = np.diff(bounds)[:n_real_fields]
    bsz = 4**nd
    out = []
    for f, (_, blocks_f, _eb, vr, size) in enumerate(members):
        nres = float(max(int(nblk_f[f]) * bsz, 1))
        ra1, rv2, rk4, sv1, sv2 = (float(s) / nres for s in sums[f])
        vv2 = max(sv2 - sv1 * sv1, 0.0)
        # host-side residual IQR on the same nd-fold difference (sampled
        # blocks only — a percentile has no prefix-sum form)
        d = blocks_f
        for ax in range(1, nd + 1):
            d = np.diff(d, axis=ax)
        dn = d.reshape(-1) / max(vr, 1e-30)
        q75, q25 = np.percentile(dn, [75.0, 25.0]) if dn.size else (0.0, 0.0)
        stats = FieldStats(
            vr=vr, size=int(size), n_blocks=int(nblk_f[f]),
            smin=float(fmin[f]), smax=float(fmax[f]),
            ra1=ra1, rv2=rv2, rk4=rk4, vv2=vv2, iqr=float(q75 - q25),
            nd=nd, r_sp=r_sp,
        )
        fp = dict(
            kind="blocks",
            digest=fingerprint_of(blocks_f, vr, int(size), r_sp),
            vr=vr, size=int(size), n=int(nblk_f[f]),
            smin=stats.smin, smax=stats.smax,
            ra1=ra1, rv2=rv2, rk4=rk4,
        )
        out.append((stats, fp))
    return out


# ---------------------------------------------------------------------------
# Predicted rate/PSNR curves + Algorithm 1 on the model
# ---------------------------------------------------------------------------


#: quadrature resolution for the expected-occupancy integrals of the SZ
#: rate model (bins grouped by residual quantile, O(1) per error bound)
_QUAD_K = 512
#: per-value overhead of the exact ZFP coder over the pure bit-plane
#: count (group tests, sign/guard bits, per-block exponent ramp) —
#: calibrated against `estimate_zfp(mode='exact')` on the bench suites
ZFP_RATE_OVERHEAD = 5.4


def _sz_bitrate_model(stats: FieldStats, eb_sz: np.ndarray) -> np.ndarray:
    """Expected SAMPLED-ESTIMATOR SZ rate at half-bin `eb_sz` under the
    Gaussian residual model (std sqrt(rv2)*vr, bin size 2*eb_sz).

    Prices exactly what `estimator.sz_bitrate_from_hist` prices, term by
    term, in expectation over an r_sp sample of n_samp residuals:

    * entropy of the delta-quantized Gaussian (analytic, capped at the
      log2(n_samp) a finite sample can exhibit) + the Miller-Madow bias
      term the estimator adds back;
    * the Chao1 Huffman-table cost: expected occupied bins / singleton /
      doubleton counts from Poissonized bin occupancy (lambda_k =
      n_samp * P(bin k)), integrated in residual-quantile space so the
      cost is O(_QUAD_K) no matter how many bins the bound implies;
    * the 64-bit escape payload for residuals beyond +-half bins.

    The result is forced monotone non-increasing in eb_sz (the physical
    truth; the occupancy quadrature can wiggle by ulps at coarse bins)."""
    sigma = math.sqrt(max(stats.rv2, 1e-38)) * max(stats.vr, 1e-30)
    n_samp = float(max(stats.n_blocks, 1) * 4**stats.nd)
    size = float(max(stats.size, 1))
    half = (est.PDF_BINS - 1) // 2
    eb_arr = np.asarray(eb_sz, np.float64)
    delta = 2.0 * np.maximum(np.atleast_1d(eb_arr), 1e-300)
    q = delta / sigma                      # bin width in residual-sigma units
    t_max = np.minimum(8.0, half * q)      # integrate to 8 sigma or the clip
    grid = (np.arange(_QUAD_K, dtype=np.float64) + 0.5) / _QUAD_K
    t = grid[None, :] * t_max[:, None]     # (n_eb, K) midpoints
    dt = (t_max / _QUAD_K)[:, None]
    phi = np.exp(-0.5 * t * t) / math.sqrt(2.0 * math.pi)
    lam = n_samp * q[:, None] * phi        # expected sample count per bin
    nbins = 2.0 * dt / q[:, None]          # bins per quadrature cell (+-t)
    n_obs = np.sum(nbins * -np.expm1(-lam), axis=1)
    f1 = np.sum(nbins * lam * np.exp(-lam), axis=1)
    f2 = np.sum(nbins * 0.5 * lam * lam * np.exp(-lam), axis=1)
    chao1 = n_obs + f1 * np.maximum(f1 - 1.0, 0.0) / (2.0 * (f2 + 1.0))
    table = est.TABLE_BITS_PER_SYMBOL * np.minimum(chao1, est.PDF_BINS) / size
    with np.errstate(divide="ignore"):
        ent = np.sum(
            2.0 * dt * phi * -np.log2(np.maximum(q[:, None] * phi, 1e-300)),
            axis=1,
        )
    ent = np.minimum(np.maximum(ent, 0.0), math.log2(max(n_samp, 2.0)))
    ent = ent + (n_obs - 1.0) / (2.0 * n_samp * est.LN2)   # Miller-Madow
    ofrac = np.array(
        [math.erfc(min(v, 30.0) / math.sqrt(2.0)) for v in half * q]
    )
    rate = ent + est.SZ_BITRATE_OFFSET + 64.0 * ofrac + table
    # enforce the physical monotonicity in the bound
    order = np.argsort(delta)
    mono = np.minimum.accumulate(rate[order])
    rate = np.empty_like(rate)
    rate[order] = mono
    return rate.reshape(eb_arr.shape) if eb_arr.shape else rate[0]


def _zfp_bitrate_model(stats: FieldStats, eb: np.ndarray) -> np.ndarray:
    """ZFP rate at bound `eb`: a significant-bit-plane count model. Of a
    4^nd block's coefficients, the AC mass sits at the residual scale
    (log2(2*sigma/eb) planes significant) and one DC coefficient at the
    value scale (log2(vr/2/eb) planes); per-value group/sign overhead and
    the header amortize over the block, plus the calibrated
    `ZFP_RATE_OVERHEAD` of the exact coder. Monotone non-increasing in
    eb."""
    bsz = 4**stats.nd
    sigma = math.sqrt(max(stats.rv2, 1e-38)) * max(stats.vr, 1e-30)
    eb = np.maximum(np.asarray(eb, np.float64), 1e-300)
    ac = np.maximum(np.log2(2.0 * sigma / eb), 0.0)
    dc = np.maximum(np.log2(0.5 * max(stats.vr, 1e-30) / eb), 0.0)
    rate = ((bsz - 1) * ac + dc) / bsz + 8.0 / bsz + 0.25
    # cap at the 32 b/v raw fallback (controller.RAW_BITS): past that the
    # selector stores raw f32 anyway
    return np.minimum(rate + ZFP_RATE_OVERHEAD, 32.0)


def _zfp_psnr_model(stats: FieldStats, eb: np.ndarray) -> np.ndarray:
    eb_rel = np.maximum(np.asarray(eb, np.float64), 1e-300) / max(
        stats.vr, 1e-30
    )
    return -20.0 * np.log10(eb_rel) + ZFP_PSNR_OFFSET


def predict_curves(stats: FieldStats, ebs) -> dict:
    """Predicted (bitrate, PSNR) curves for both codecs at absolute error
    bounds `ebs` — the black-box curves of arXiv 2305.08801, from moments
    alone. SZ's PSNR is exact Eq. (11); rates are models."""
    ebs = np.asarray(ebs, np.float64)
    return dict(
        eb=ebs,
        br_sz=_sz_bitrate_model(stats, ebs),
        br_zfp=_zfp_bitrate_model(stats, ebs),
        psnr_sz=np.asarray(
            -20.0 * np.log10(np.maximum(ebs / max(stats.vr, 1e-30), 1e-300))
            + 10.0 * math.log10(3.0)
        ),
        psnr_zfp=_zfp_psnr_model(stats, ebs),
    )


def confidence(stats: FieldStats) -> float:
    """How much to trust the moment model for this field, in [0, 1].

    Hard zeros: degenerate value range, non-finite or non-positive
    residual variance (constant fields). Soft factors: sample size
    (tiny fields -> noisy moments), residual kurtosis above the
    Gaussian/Laplacian band (heavy tails break the entropy model), and
    the |.|-to-std shape ratio drifting from the Gaussian sqrt(2/pi)
    (multi-modal / spiky residuals)."""
    if not (stats.vr > 0.0 and math.isfinite(stats.vr)):
        return 0.0
    if not (stats.rv2 > 0.0 and math.isfinite(stats.rv2)):
        return 0.0
    if not math.isfinite(stats.rk4):
        return 0.0
    c_size = min(1.0, stats.size / float(MIN_CONFIDENT_SIZE))
    c_tail = 1.0 / (1.0 + max(0.0, stats.kurtosis - 6.0) / KURTOSIS_SCALE)
    shape = stats.ra1 / (math.sqrt(stats.rv2) * math.sqrt(2.0 / math.pi))
    c_shape = 1.0 / (1.0 + 2.0 * abs(math.log(max(shape, 1e-12))))
    return c_size * c_tail * c_shape


def predict_selection(
    stats: FieldStats,
    eb_abs: float,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
) -> _sel.Selection:
    """Algorithm 1 replayed on the predicted curves: ZFP PSNR at the
    bound -> iso-PSNR SZ half-bin (same PSNR_MATCH_QUANTUM snap and clip
    as the sampled path) -> min predicted rate."""
    eb = float(eb_abs)
    psnr_z = float(_zfp_psnr_model(stats, eb))
    psnr_q = round(psnr_z / est.PSNR_MATCH_QUANTUM) * est.PSNR_MATCH_QUANTUM
    delta = max(stats.vr, 1e-30) * math.sqrt(12.0) * 10.0 ** (-psnr_q / 20.0)
    eb_sz = min(max(delta / 2.0, eb * 1e-6), eb)
    br_sz = float(_sz_bitrate_model(stats, eb_sz))
    br_zfp = float(_zfp_bitrate_model(stats, eb))
    codec = _sel._pick_codec(br_sz, br_zfp, codecs)
    return _sel.Selection(
        codec, eb, eb_sz, br_sz, br_zfp, psnr_z, stats.vr, stats.r_sp
    )


def select_many_predicted(
    fields,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    r_sp: float | None = None,
    transform: str = "zfp",
    codecs: tuple[str, ...] | None = None,
    *,
    policy=None,
    confidence_threshold: float = CONFIDENCE_THRESHOLD,
) -> tuple[list[_sel.Selection], list[str]]:
    """`select_many` with the predictor in front: confident fields take
    the moment-model decision, low-confidence fields fall back to the
    sampled estimator, degenerate fields keep the raw fallback. Returns
    (selections, routes) with routes[i] in
    {'predicted', 'sampled', 'degenerate'}.

    Opt-in by design: predicted decisions follow the model, not the
    sampled estimate, so this is NOT the path behind the frozen goldens
    or `compress_pytree` — it serves overhead-critical in-situ loops that
    accept model-grade selection accuracy (paper §6: the two codecs'
    rates differ by >1 b/v on most fields, so model error rarely flips)."""
    if policy is not None:
        if policy.mode != "fixed_accuracy":
            raise ValueError(
                "select_many_predicted takes a fixed_accuracy policy, got "
                f"{policy.mode!r}"
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
    results: list[_sel.Selection | None] = [None] * len(fields)
    groups = _sel._build_select_members(
        fields, range(len(fields)), results, eb_abs, eb_rel, r_sp, transform,
        codecs,
    )
    routes = ["degenerate" if r is not None else "" for r in results]
    fallback: dict[int, list] = {}
    for nd, members in groups.items():
        stats = stats_for_members(nd, members, r_sp)
        for m, (s, _fp) in zip(members, stats):
            i = m[0]
            if confidence(s) >= confidence_threshold:
                results[i] = predict_selection(s, m[2], codecs)
                routes[i] = "predicted"
            else:
                fallback.setdefault(nd, []).append(m)
                routes[i] = "sampled"
    if fallback:
        _sel._run_select_batches(fallback, results, r_sp, transform, codecs)
    return results, routes  # type: ignore[return-value]


__all__ = [
    "CONFIDENCE_THRESHOLD",
    "FieldStats",
    "confidence",
    "fingerprint_of",
    "predict_curves",
    "predict_selection",
    "select_many_predicted",
    "stats_for_members",
]
