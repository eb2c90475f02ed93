"""Quality-target controller: fixed-PSNR and fixed-ratio modes (DESIGN.md §7).

The selection engine (DESIGN.md §1) answers "which codec is cheapest at
this error bound" — but callers usually hold a *quality* target ("give me
60 dB", "give me 8x"), not an error bound. This module inverts the
estimator math of DESIGN.md §4–§5 to solve for the per-field error bound
that meets the target, then hands the resulting `Selection` to the
ordinary encoders. There are NO trial compressions anywhere in the search
loop — the objective is always the *estimated* (or sample-measured)
rate-distortion curve:

* ``fixed_psnr`` — iso-distortion at the target. The closed-form
  inversion of Eq. (10) (`estimator.sz_delta_for_psnr`, snapped to
  `estimator.PSNR_MATCH_QUANTUM`) seeds SZ's bin size; a few secant steps
  against the *measured* quantization error of the sampled blocks absorb
  what the uniform-noise model misses (fields with constant runs land up
  to ~3 dB hot otherwise). ZFP's bound walks its estimated-PSNR staircase
  the same way. The codec with the smaller estimated rate *within the
  PSNR tolerance band* wins — Algorithm 1's iso-PSNR/min-rate rule,
  anchored at the caller's target instead of ZFP's achieved-at-eb PSNR.
* ``fixed_ratio`` — iso-rate. Both codecs are driven to the byte budget
  by a high-rate-model seed (rate moves ~1 bit/value per octave of bound)
  plus clamped secant steps, and the codec with the higher estimated PSNR
  at the budget wins — the rate-distortion dual of Algorithm 1.
* ``fixed_ssim`` / ``fixed_correlation`` / ``fixed_ks`` — metric targets
  (DESIGN.md §7.4). Every metric is a monotone function of the error
  variance, so `core/quality.py` converts the metric target into a
  per-field *equivalent-PSNR* target (closed form for SSIM/correlation
  from the sampled variance; a bisection on the sample-measured KS curve)
  and the fixed_psnr machinery solves it — same seeds, same secant, same
  min-rate-at-target codec choice, zero trial compressions.
* ``fixed_accuracy`` — the paper's bound-centric mode, delegated to
  `select_many` so all the modes share one call signature.

All candidate bounds for all fields are evaluated by ONE jitted launch
per round: the packed block batches of `select_many` gain a vmapped
candidate axis (`_sweep_jitted`), so each round is a `(1, fields)`-slot
program over blocks gathered once per field. fixed_psnr rounds use a
*light* sweep that returns only PSNR outputs, letting XLA dead-code-
eliminate the exact-coder bit count and the SZ entropy sort — the two
dominant costs — so the whole solve stays well under the encoders' time.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache as _lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import codecs as _codecs
from . import estimator as est
from . import quality as qual
from .policy import TARGET_FIELD, Policy, policy_from_kwargs
from .selector import (
    MAX_BATCH_FIELDS,
    Selection,
    _degenerate_selection,
    _fold_ndim,
    _max_batch_blocks,
    _next_pow2,
    select_many,
)

#: the codecs' working dtype is float32, so ratio targets are defined
#: against 32 bits/value (matching `compression_ratio`)
RAW_BITS = 32.0

#: fixed_psnr: ZFP is eligible only when its estimated PSNR lands within
#: this many dB above the target — the bit-plane staircase otherwise
#: overshoots by up to ~6 dB/plane, and "hit the target" beats "free extra
#: quality the caller did not ask to pay rate for". SZ's measured-error
#: refinement lands on the target by construction, so SZ always competes.
PSNR_TOL_DB = 0.5
#: a probe counts as meeting a PSNR target when it clears it minus this
#: slack (absorbs sampling noise without chasing ulps)
PSNR_SLACK_DB = 0.25

#: fixed_ratio: a codec is eligible when its estimated rate is within this
#: relative window of the budget (the solve keeps rate <= budget; this
#: rejects staircase undershoot past the ratio tolerance).
RATIO_TOL = 0.10
#: a rate probe counts as meeting the budget up to this relative overage —
#: rejecting a probe 0.2% over the budget in favor of one 20% under it
#: would miss the ratio window from the other side
RATE_SLACK = 0.02

#: the §4 SZ estimate carries the paper's flat +0.5 bits/value Huffman
#: cushion — a selection-side worst case, not what the byte coder pays. A
#: rate *target* cannot absorb a ~0.4-bit bias (it lands straight in the
#: achieved ratio), so the controller retargets with an empirical overhead
#: curve: near zero above ~1 bit/value of residual entropy, rising toward
#: the 1-bit/symbol Huffman floor as the PDF peaks (DESIGN.md §7).
SZ_HUFF_FLOOR = 0.08
SZ_HUFF_PEAK_SLOPE = 0.85

#: high-rate-model slopes used to seed and clamp the secant steps: one
#: octave of bound costs ~1 bit/value (Eq. (9) at high rate; exactly one
#: bit-plane for ZFP) == ~6.02 dB (Eq. (11))
DB_PER_OCTAVE = 20.0 * math.log10(2.0)
#: secant-slope clamps, [steepest, shallowest] (negative: metrics are
#: nonincreasing in the bound)
PSNR_SLOPE_CLAMP = (-30.0, -1.0)
RATE_SLOPE_CLAMP = (-4.0, -0.25)

#: refinement evals after the seed eval, by mode (fixed_psnr and the
#: §7.4 metric modes ride light sweeps; fixed_ratio rounds are full-rate
#: probes; every mode ends in one full pricing eval)
DEFAULT_ROUNDS = {
    "fixed_psnr": 3,
    "fixed_ratio": 3,
    "fixed_ssim": 3,
    "fixed_correlation": 3,
    "fixed_ks": 3,
}


@dataclass
class TargetSolution:
    """One field's solved target: the `Selection` to encode with, plus the
    estimates the solve ended on (what the controller *believes* it hit)."""

    selection: Selection
    mode: str
    target: float        # dB (fixed_psnr), ratio (fixed_ratio), eb (fixed_accuracy),
                         # metric value (fixed_ssim / fixed_correlation / fixed_ks)
    est_psnr: float      # estimated/measured PSNR of the chosen codec
    est_bitrate: float   # estimated bits/value of the chosen codec
    on_target: bool      # False when the solve could only get best-effort close
    #: predicted metric value of the chosen codec (§7.4 metric modes only;
    #: None elsewhere — the default keeps pre-metric cache entries and
    #: manifests deserializing unchanged)
    est_metric: float | None = None

    @property
    def est_ratio(self) -> float:
        return RAW_BITS / max(self.est_bitrate, 1e-6)


def _sz_coder_rate(br_est: np.ndarray) -> np.ndarray:
    """Map the §4 SZ estimate (entropy + flat +0.5 cushion) to the rate the
    byte coder actually pays: entropy + an overhead that decays to
    `SZ_HUFF_FLOOR` for rich residual PDFs and grows to the 1-bit/symbol
    Huffman floor as the PDF peaks. Monotone in `br_est` (slope >= 0.15),
    so the root-finding invariant survives the correction."""
    ent = np.maximum(np.asarray(br_est, np.float64) - est.SZ_BITRATE_OFFSET, 0.0)
    return ent + np.maximum(1.0 - SZ_HUFF_PEAK_SLOPE * ent, SZ_HUFF_FLOOR)


# ---------------------------------------------------------------------------
# The sweep: batched estimators + a vmapped candidate axis
# ---------------------------------------------------------------------------


def _sz_measured_psnr(nohalo, seg, bounds, delta_f, vr_f):
    """PSNR of the actual quantization error `x - delta*round(x/delta)` on
    the sampled blocks — what the SZ codec really achieves, including the
    sub-uniform error of fields with constant runs (values sitting exactly
    on bin centers), which the Eq. (11) model misses by up to ~3 dB."""
    nd = nohalo.ndim - 1
    n_s = nohalo.shape[0]
    d = delta_f[seg].reshape((-1,) + (1,) * nd)
    err = nohalo - d * jnp.round(nohalo / d)
    vr64 = jnp.maximum(vr_f, 1e-30)
    err2_blk = jnp.sum(jnp.square(err).reshape(n_s, -1), axis=1) / jnp.square(
        vr64[seg]
    )
    err2_f = est.field_sums(err2_blk, bounds)
    n_f = (bounds[1:] - bounds[:-1]).astype(jnp.float32) * float(4**nd)
    mse_over_vr2 = err2_f / jnp.maximum(n_f, 1.0)
    return -10.0 * jnp.log10(jnp.maximum(mse_over_vr2, 1e-60))


@_lru_cache(maxsize=64)
def _sweep_jitted(
    nd: int, n_blocks: int, n_fields: int, n_cand: int, transform: str, kind: str
):
    """Jitted (candidates x fields) estimator sweep over one packed batch.

    vmap adds the candidate axis to the per-field bound arrays only — the
    block batch is closed over, so XLA hoists the bound-independent work
    (gather view, exponents, BOT coefficients) out of the candidate loop
    instead of materializing `n_cand` copies of the blocks. kind='light'
    returns only the PSNR outputs, and XLA dead-code-eliminates the
    exact-coder bit count and the SZ entropy sort — the expensive
    stages — making fixed_psnr refinement rounds cheap; kind='rate' swaps
    the 31-plane exact ZFP coder for the one-pass closed-form block_bits
    model (fixed_ratio refinement probes); kind='full' is decision-grade.
    Cached per (ndim, padded blocks, padded fields, candidates, kind),
    same pow2 bucketing as `select_many` (DESIGN.md §1).
    """

    def eval_one(eb_f, delta_f, halo, seg, bounds, vr_f, size_f):
        # ZFP at eb_f and SZ at delta_f are independent estimators on the
        # same blocks; one slot evaluates both (DESIGN.md §4–§5)
        nohalo = halo[(slice(None),) + (slice(1, None),) * nd]
        zfp_mode = "model" if kind == "rate" else "exact"
        e_zfp = est.estimate_zfp_many(
            nohalo, seg, bounds, eb_f, vr_f, transform, mode=zfp_mode
        )
        ps_meas = _sz_measured_psnr(nohalo, seg, bounds, delta_f, vr_f)
        if kind == "light":
            return e_zfp.psnr, ps_meas
        e_sz = est.estimate_sz_many(halo, seg, bounds, delta_f, vr_f, size_f)
        return e_sz.bitrate, e_sz.psnr, e_zfp.bitrate, e_zfp.psnr, ps_meas

    def solve_sweep(halo, seg, bounds, eb_cf, delta_cf, vr_f, size_f):
        return jax.vmap(eval_one, in_axes=(0, 0, None, None, None, None, None))(
            eb_cf, delta_cf, halo, seg, bounds, vr_f, size_f
        )

    return jax.jit(solve_sweep)


@dataclass
class _Member:
    idx: int             # position in the caller's field list
    blocks: np.ndarray   # halo blocks, (n_blocks, 5, ..)
    vr: float
    size: int


class _Sweep:
    """One packed batch (same layout as `selector._select_batch`) exposing
    `full` / `light` candidate sweeps. Inputs are (n_cand, n_real_fields)
    per-field bounds (eb for ZFP, bin size delta for SZ); outputs are
    (n_cand, n_real_fields) arrays."""

    def __init__(self, nd: int, members: list[_Member], transform: str):
        self.nd, self.transform = nd, transform
        halo = np.concatenate([m.blocks for m in members], axis=0)
        seg = np.concatenate(
            [np.full(len(m.blocks), f, dtype=np.int32) for f, m in enumerate(members)]
        )
        n_real_blocks, self.n_real_fields = len(seg), len(members)
        self.n_blocks = _next_pow2(n_real_blocks)
        self.n_fields = _next_pow2(self.n_real_fields + 1)
        pad = self.n_blocks - n_real_blocks
        if pad:
            halo = np.concatenate([halo, np.zeros((pad,) + halo.shape[1:], np.float32)])
            seg = np.concatenate([seg, np.full(pad, self.n_fields - 1, np.int32)])
        bounds = np.zeros(self.n_fields + 1, np.int32)
        bounds[1 : self.n_real_fields + 1] = np.cumsum([len(m.blocks) for m in members])
        bounds[self.n_real_fields + 1 :] = n_real_blocks
        bounds[self.n_fields] = self.n_blocks
        vr_p = np.ones(self.n_fields, np.float32)
        vr_p[: self.n_real_fields] = [m.vr for m in members]
        size_p = np.ones(self.n_fields, np.float32)
        size_p[: self.n_real_fields] = [m.size for m in members]
        self._args = (
            jnp.asarray(halo), jnp.asarray(seg), jnp.asarray(bounds),
            jnp.asarray(vr_p), jnp.asarray(size_p),
        )

    def _run(self, eb_c, delta_c, kind: str):
        n_cand = eb_c.shape[0]
        ebp = np.ones((n_cand, self.n_fields), np.float32)
        ebp[:, : self.n_real_fields] = np.maximum(eb_c, 1e-38)
        dp = np.ones((n_cand, self.n_fields), np.float32)
        dp[:, : self.n_real_fields] = np.maximum(delta_c, 1e-38)
        halo, seg, bounds, vr, size = self._args
        fn = _sweep_jitted(
            self.nd, self.n_blocks, self.n_fields, n_cand, self.transform, kind
        )
        out = fn(halo, seg, bounds, jnp.asarray(ebp), jnp.asarray(dp), vr, size)
        return tuple(np.asarray(o)[:, : self.n_real_fields] for o in out)

    def full(self, eb_c, delta_c):
        """(br_sz, psnr_sz_model, br_zfp, psnr_zfp, psnr_sz_measured)."""
        return self._run(eb_c, delta_c, "full")

    def rate(self, eb_c, delta_c):
        """Same 5-tuple with the one-pass block_bits ZFP coder model —
        probe-grade rates for the fixed_ratio refinement rounds."""
        return self._run(eb_c, delta_c, "rate")

    def light(self, eb_c, delta_c):
        """(psnr_zfp, psnr_sz_measured) only — coder bits / entropy DCE'd."""
        return self._run(eb_c, delta_c, "light")


# ---------------------------------------------------------------------------
# Vectorized secant root-finding on a nonincreasing sampled curve
# ---------------------------------------------------------------------------


class _Secant:
    """Per-field secant iteration for `g(x) = target` where g is
    nonincreasing in x (= log2 bound) and only eval-able in batches.

    Tracks the best *feasible* probe (g clears the target: `g >= target`
    for PSNR, `g <= target` for rate — pass `ge=False`) closest to the
    target, plus a bracket for safeguarding; steps are clamped to the
    model slope range so a flat staircase section cannot fling the
    iterate."""

    def __init__(self, x0, g0, target, slope0, slope_clamp, ge: bool, x_lo, x_hi):
        F = len(x0)
        self.t, self.ge = np.asarray(target, np.float64), ge
        self.slope0, self.clamp = slope0, slope_clamp
        self.x_lo, self.x_hi = x_lo, x_hi
        self.xp = np.full(F, np.nan)
        self.gp = np.full(F, np.nan)
        self.xc, self.gc = np.asarray(x0, np.float64), np.asarray(g0, np.float64)
        # bracket: blo = largest x still clearing, bhi = smallest x missing
        self.blo = np.full(F, -np.inf)
        self.bhi = np.full(F, np.inf)
        self.x_best = np.full(F, np.nan)
        self.g_best = np.full(F, np.nan)
        self._absorb(self.xc, self.gc)

    def _clears(self, g):
        if self.ge:
            return g >= self.t - PSNR_SLACK_DB
        return g <= self.t * (1.0 + RATE_SLACK)

    def _absorb(self, x, g):
        ok = self._clears(g)
        # bracket sides follow g's direction, not feasibility: g is
        # nonincreasing in x, so probes with g above the target sit below
        # the root (-> blo) and probes below it sit above (-> bhi)
        above = ok if self.ge else ~ok
        self.blo = np.where(above, np.maximum(self.blo, x), self.blo)
        self.bhi = np.where(~above, np.minimum(self.bhi, x), self.bhi)
        # feasible-best: the clearing probe closest to the target
        gap = np.abs(g - self.t)
        better = ok & (np.isnan(self.g_best) | (gap < np.abs(self.g_best - self.t)))
        self.x_best = np.where(better, x, self.x_best)
        self.g_best = np.where(better, g, self.g_best)

    def propose(self):
        dx = self.xc - self.xp
        dg = self.gc - self.gp
        slope = np.where(np.abs(dx) > 1e-9, dg / np.maximum(np.abs(dx), 1e-9) * np.sign(dx), self.slope0)
        slope = np.clip(np.nan_to_num(slope, nan=self.slope0), *self.clamp)
        xn = self.xc + (self.t - self.gc) / slope
        # safeguard: project into the bracket when the secant leaves it
        have = np.isfinite(self.blo) & np.isfinite(self.bhi)
        mid = 0.5 * (self.blo + self.bhi)
        xn = np.where(have & ((xn <= self.blo) | (xn >= self.bhi)), mid, xn)
        return np.clip(xn, self.x_lo, self.x_hi)

    def step(self, xn, gn):
        self.xp, self.gp = self.xc, self.gc
        self.xc, self.gc = np.asarray(xn, np.float64), np.asarray(gn, np.float64)
        self._absorb(self.xc, self.gc)

    @property
    def found(self):
        return ~np.isnan(self.x_best)


# ---------------------------------------------------------------------------
# Mode solvers (vectorized across the fields of one batch)
# ---------------------------------------------------------------------------


#: refinement probes run on every k-th gathered block (the secant only
#: needs the curve's trend; the final pricing eval uses the full sample)
REFINE_STRIDE = 2


def _warm_seeds(warm, x0_s, x0_z, x_lo, x_hi):
    """Overlay cached warm-start seeds (log2 bounds, NaN = cold) onto the
    model seeds, clipped to the solver's x-range. With `warm=None` or
    all-NaN this returns the model seeds unchanged, so the cold program
    is untouched."""
    if warm is None:
        return x0_s, x0_z
    warm_s, warm_z = warm
    x0_s = np.where(
        np.isfinite(warm_s), np.clip(warm_s, x_lo, x_hi), x0_s
    )
    x0_z = np.where(
        np.isfinite(warm_z), np.clip(warm_z, x_lo, x_hi), x0_z
    )
    return x0_s, x0_z


def _solve_fixed_psnr(
    sweep: _Sweep, refine: _Sweep, vr: np.ndarray, target, rounds: int,
    r_sp: float, allowed: tuple[str, ...] = _codecs.DEFAULT_CODECS,
    warm=None,
) -> list[tuple[Selection, float, float, bool]]:
    """Per field: (Selection, est_psnr, est_bitrate, on_target).

    Seed: SZ bin size from the closed-form inversion of Eq. (10); ZFP
    bound at delta*/2 — or, per field, the previous save's solved bound
    when the decision cache offers a warm seed (`warm`, DESIGN.md §8):
    the secant then starts next to the root it found last step instead of
    on the model curve. Refine: `rounds` light-sweep secant steps drive
    both codecs' *observed* curves (measured quantization error for SZ,
    estimated truncation PSNR for ZFP) onto the target; one final full
    eval prices the two solutions for the min-rate choice.

    `target` is a scalar dB value, or a per-field (F,) array — the §7.4
    metric modes feed per-field equivalent-PSNR targets through the same
    solve (the secant, snap and eligibility tests are all elementwise, so
    the scalar path's numerics are untouched).
    """
    tq = (
        np.round(np.asarray(target, np.float64) / est.PSNR_MATCH_QUANTUM)
        * est.PSNR_MATCH_QUANTUM
    )
    delta_star = np.asarray(
        est.sz_delta_for_psnr(
            jnp.asarray(target, jnp.float32), jnp.asarray(vr, np.float32)
        ),
        np.float32,
    )
    lvr = np.log2(np.maximum(vr, 1e-30)).astype(np.float64)
    ld0 = np.log2(np.maximum(delta_star, 1e-38)).astype(np.float64)
    x0_s, x0_z = _warm_seeds(warm, ld0, ld0 - 1.0, lvr - 30.0, lvr + 1.0)
    pz0, ps0 = refine.light(np.exp2(x0_z)[None].astype(np.float32),
                            np.exp2(x0_s)[None].astype(np.float32))
    s_sz = _Secant(x0_s, ps0[0], tq, -DB_PER_OCTAVE, PSNR_SLOPE_CLAMP,
                   ge=True, x_lo=lvr - 30.0, x_hi=lvr + 1.0)
    s_z = _Secant(x0_z, pz0[0], tq, -DB_PER_OCTAVE, PSNR_SLOPE_CLAMP,
                  ge=True, x_lo=lvr - 30.0, x_hi=lvr + 1.0)
    for _ in range(rounds):
        xs, xz = s_sz.propose(), s_z.propose()
        pz, ps = refine.light(np.exp2(xz)[None].astype(np.float32),
                              np.exp2(xs)[None].astype(np.float32))
        s_z.step(xz, pz[0])
        s_sz.step(xs, ps[0])
    # final bounds: feasible-best, falling back to the seed (the
    # closed-form, model-exact bin for SZ absent a warm override)
    x_s = np.where(s_sz.found, s_sz.x_best, x0_s)
    x_z = np.where(s_z.found, s_z.x_best, x0_z)
    br_sz_raw, _, br_zfp, ps_zfp, ps_meas = sweep.full(
        np.exp2(x_z)[None].astype(np.float32), np.exp2(x_s)[None].astype(np.float32)
    )
    br_s = _sz_coder_rate(br_sz_raw[0])
    br_z, ps_z, ps_s = br_zfp[0], ps_zfp[0], ps_meas[0]
    zfp_ok = s_z.found & (ps_z <= tq + PSNR_TOL_DB) & (ps_z >= tq - PSNR_SLACK_DB)
    out = []
    F = len(vr)
    tq_f = np.broadcast_to(np.asarray(tq, np.float64), (F,))
    for f in range(F):
        tqf = float(tq_f[f])
        eb_s = float(np.exp2(x_s[f])) / 2.0
        cands = []
        if "sz" in allowed:
            cands.append(("sz", float(br_s[f]), float(ps_s[f]), eb_s))
        if zfp_ok[f] and "zfp" in allowed:
            cands.append(("zfp", float(br_z[f]), float(ps_z[f]), float(np.exp2(x_z[f]))))
        if not cands:
            # allowlist left only ZFP and its staircase missed the band:
            # best-effort on its solved bound (flagged off-target below)
            cands = [("zfp", float(br_z[f]), float(ps_z[f]), float(np.exp2(x_z[f])))]
        codec, br, ps, eb = min(cands, key=lambda c: c[1])
        if br >= RAW_BITS:
            # incompressible at this quality — raw is exact, PSNR = inf
            codec, br, ps = "raw", RAW_BITS, math.inf
        # raw is lossless (target exceeded by construction); a lossy codec
        # is on-target only when it actually landed within the contract
        on_target = codec == "raw" or abs(ps - tqf) <= 2.0 * PSNR_TOL_DB
        sel = Selection(
            codec, eb, eb_s, float(br_s[f]), float(br_z[f]),
            ps if codec != "raw" else tqf, float(vr[f]), r_sp,
        )
        out.append((sel, ps, br, on_target))
    return out


def _solve_fixed_ratio(
    sweep: _Sweep, refine: _Sweep, vr: np.ndarray, target: float, rounds: int,
    r_sp: float, allowed: tuple[str, ...] = _codecs.DEFAULT_CODECS,
    warm=None,
) -> list[tuple[Selection, float, float, bool]]:
    """Per field: (Selection, est_psnr, est_bitrate, on_target).

    Both codecs are driven to `rate <= RAW_BITS/target` (maximum quality
    inside the byte budget) from a mid-curve seed via the ~1 bit/octave
    high-rate model plus clamped secant steps; the higher-PSNR codec at
    the budget wins — iso-rate selection, the dual of Algorithm 1. SZ's
    entropy curve is continuous in the bin size, so it can land inside
    the ratio window even where ZFP's bit-plane staircase skips it.
    """
    br_t = RAW_BITS / float(target)
    lvr = np.log2(np.maximum(vr, 1e-30)).astype(np.float64)
    x0 = lvr - 8.0
    x0_s, x0_z = _warm_seeds(warm, x0, x0, lvr - 26.0, lvr)
    br_s0, _, br_z0, _, _ = refine.rate(
        np.exp2(x0_z)[None].astype(np.float32),
        np.exp2(x0_s)[None].astype(np.float32),
    )
    s_sz = _Secant(x0_s, _sz_coder_rate(br_s0[0]), br_t, -1.0, RATE_SLOPE_CLAMP,
                   ge=False, x_lo=lvr - 26.0, x_hi=lvr)
    s_z = _Secant(x0_z, br_z0[0], br_t, -1.0, RATE_SLOPE_CLAMP,
                  ge=False, x_lo=lvr - 26.0, x_hi=lvr)
    for _ in range(rounds):
        xs, xz = s_sz.propose(), s_z.propose()
        br_s, _, br_z, _, _ = refine.rate(np.exp2(xz)[None].astype(np.float32),
                                          np.exp2(xs)[None].astype(np.float32))
        s_sz.step(xs, _sz_coder_rate(br_s[0]))
        s_z.step(xz, br_z[0])
    # final bounds: feasible-best; an unreachable budget rails at the
    # loosest bound evaluated (best effort, flagged off-target below).
    # fmax, not maximum: with rounds=0 no secant step ran and xp is NaN
    x_s = np.where(s_sz.found, s_sz.x_best, np.fmax(s_sz.xc, s_sz.xp))
    x_z = np.where(s_z.found, s_z.x_best, np.fmax(s_z.xc, s_z.xp))

    def _price(xs, xz):
        br_sz_raw, _, br_zfp, ps_zfp, ps_meas = sweep.full(
            np.exp2(xz)[None].astype(np.float32), np.exp2(xs)[None].astype(np.float32)
        )
        return _sz_coder_rate(br_sz_raw[0]), br_zfp[0], ps_zfp[0], ps_meas[0]

    br_s, br_z, ps_z, ps_s = _price(x_s, x_z)
    # polish: the strided refine probes can sit a few % off the
    # full-sample curve; up to two corrective steps against the
    # full-sample price recenter fields that landed outside the rate
    # window (the first uses the ~1 bit/octave model slope, the second an
    # empirical slope from the first correction)
    lo_w, hi_w = br_t / (1.0 + RATIO_TOL), br_t * (1.0 + RATE_SLACK)
    prev = None
    for _ in range(2):
        # no `found` gate: a field whose refine probes never cleared the
        # budget (strided-sample bias, unreachable target) still gets
        # walked toward it; the x-clip bounds genuinely unreachable ones
        need_s = (br_s > hi_w) | (br_s < lo_w)
        need_z = (br_z > hi_w) | (br_z < lo_w)
        if not (need_s.any() or need_z.any()):
            break
        slope_s = np.full_like(br_s, -1.0)
        slope_z = np.full_like(br_z, -1.0)
        if prev is not None:
            px_s, pbr_s, px_z, pbr_z = prev
            ds, dz = x_s - px_s, x_z - px_z
            slope_s = np.where(np.abs(ds) > 1e-9, (br_s - pbr_s) / np.where(np.abs(ds) > 1e-9, ds, 1.0), -1.0)
            slope_z = np.where(np.abs(dz) > 1e-9, (br_z - pbr_z) / np.where(np.abs(dz) > 1e-9, dz, 1.0), -1.0)
            slope_s = np.clip(slope_s, -4.0, -0.1)
            slope_z = np.clip(slope_z, -4.0, -0.1)
        prev = (x_s.copy(), br_s.copy(), x_z.copy(), br_z.copy())
        x_s = np.clip(np.where(need_s, x_s + (br_t - br_s) / slope_s, x_s), lvr - 26.0, lvr)
        x_z = np.clip(np.where(need_z, x_z + (br_t - br_z) / slope_z, x_z), lvr - 26.0, lvr)
        br_s, br_z, ps_z, ps_s = _price(x_s, x_z)
    out = []
    for f in range(len(vr)):
        cands = []
        for name, br, ps, bound in (
            ("sz", float(br_s[f]), float(ps_s[f]), float(np.exp2(x_s[f])) / 2.0),
            ("zfp", float(br_z[f]), float(ps_z[f]), float(np.exp2(x_z[f]))),
        ):
            if name not in allowed:
                continue
            in_window = (br <= br_t * (1.0 + RATE_SLACK)) and (
                br >= br_t / (1.0 + RATIO_TOL)
            )
            cands.append((name, br, ps, bound, in_window))
        eligible = [c for c in cands if c[4]]
        if eligible:
            codec, br, ps, bound, _ = max(eligible, key=lambda c: c[2])
            on_target = True
        else:
            # best effort: closest estimated rate to the budget
            codec, br, ps, bound, _ = min(
                cands, key=lambda c: abs(math.log(max(c[1], 1e-6) / br_t))
            )
            on_target = False
        if br >= RAW_BITS:
            codec, br, ps = "raw", RAW_BITS, math.inf
            on_target = target <= 1.0 + 1e-9
        eb_s = float(np.exp2(x_s[f])) / 2.0
        sel = Selection(
            codec, bound if codec == "zfp" else eb_s, eb_s,
            float(br_s[f]), float(br_z[f]),
            ps if codec != "raw" else 0.0, float(vr[f]), r_sp,
        )
        out.append((sel, ps, br, on_target))
    return out


def _solve_fixed_metric(
    sweep: _Sweep, refine: _Sweep, batch: list[_Member], nd: int,
    vr: np.ndarray, mode: str, target: float, rounds: int, r_sp: float,
    allowed: tuple[str, ...] = _codecs.DEFAULT_CODECS, warm=None,
) -> list[tuple[Selection, float, float, bool, float]]:
    """Per field: (Selection, est_psnr, est_bitrate, on_target, est_metric)
    for the §7.4 metric modes (fixed_ssim / fixed_correlation / fixed_ks).

    Every supported metric is a monotone function of the error variance
    given the field's sampled statistics (`core/quality.py`), so the solve
    is: (1) compute per-field metric sufficient statistics from the same
    halo blocks the rate estimators use; (2) invert the metric target into
    a per-field equivalent-PSNR target — closed form for SSIM/correlation,
    an interpolation on the sample-measured KS curve for fixed_ks; (3) run
    the fixed-PSNR solve on the per-field target array (closed-form seed,
    clamped light-sweep secant, min-rate codec choice *at the metric
    target* — Algorithm 1's rule anchored on the caller's contract instead
    of at matched eb); (4) read the achieved metric back off the solved
    PSNR for telemetry and the on-target check. Zero trial compressions,
    same launch profile as fixed_psnr.
    """
    metric = qual.MODE_METRIC[mode]
    stats = [qual.stats_from_blocks(m.blocks, nd, m.vr) for m in batch]
    psnr_t = np.asarray(
        [qual.equivalent_psnr(metric, target, s) for s in stats], np.float64
    )
    solved = _solve_fixed_psnr(
        sweep, refine, vr, psnr_t, rounds, r_sp, allowed, warm=warm
    )
    tol = qual.TOLERANCE[metric]
    out = []
    for f, (sel, ps, br, _on) in enumerate(solved):
        if sel.codec == "raw":
            m_a = qual.LOSSLESS_VALUE[metric]
            on = True
        else:
            m_a = qual.metric_from_psnr(metric, ps, stats[f])
            # SSIM/correlation are floors (overshoot is free quality), KS a
            # ceiling; within-tolerance misses still count as on target
            on = qual.metric_gap(metric, m_a, float(target)) <= tol
        out.append((sel, ps, br, on, float(m_a)))
    return out


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def solve_many(
    fields,
    policy: Policy | str,
    *,
    target_psnr: float | None = None,
    target_ratio: float | None = None,
    eb_abs: float | None = None,
    eb_rel: float | None = None,
    r_sp: float | None = None,
    transform: str = "zfp",
    rounds: int | None = None,
    cache=None,
    names=None,
) -> list[TargetSolution]:
    """Solve the quality target for MANY fields with batched launches.

    `policy` is the quality contract (`core/policy.py`, DESIGN.md §2):

    * `Policy.fixed_psnr(db)`   — target dB, relative to each field's
                                  value range (as everywhere else);
    * `Policy.fixed_ratio(x)`   — x vs 32-bit raw;
    * `Policy.fixed_ssim(s)` / `Policy.fixed_correlation(rho)` /
      `Policy.fixed_ks(d)`      — §7.4 metric targets, inverted to
                                  per-field equivalent-PSNR targets via
                                  `core/quality.py` (solutions carry the
                                  predicted metric in `est_metric`);
    * `Policy.fixed_accuracy(...)` — delegates to `select_many` (the
                                  paper's bound-centric path) so all the
                                  modes share one entry point.

    The policy's `codecs` allowlist restricts which registered codecs
    compete (DESIGN.md §2.1); its `r_sp` sets the estimator sampling rate.
    Passing a mode *string* plus the old target/eb/r_sp keyword arguments
    is deprecated — the shim maps them onto the equivalent `Policy` (and
    therefore solves bit-identically) but warns.

    Fields that cannot carry a target — too small, constant, NaN-poisoned —
    fall back to raw exactly like `select_many` (`on_target=False` for
    fixed_ratio, since raw pins their ratio to 1). Fields whose sample
    would exceed a launch's block cap are strided down instead of being
    kicked to a per-field path, so every field stays inside the batched
    sweep. Returns one `TargetSolution` per input field, in order.

    `cache`/`names` enable the warm path (a `DecisionCache`, DESIGN.md
    §8): fingerprint-validated fields replay the previous save's
    `TargetSolution` without entering the sweep at all; invalidated
    entries can additionally warm-start the secant from the previously
    solved bound when the cache has `warm_start=True`.
    """
    if isinstance(policy, str):
        policy = policy_from_kwargs(
            "solve_many", mode=policy, eb_abs=eb_abs, eb_rel=eb_rel,
            target_psnr=target_psnr, target_ratio=target_ratio, r_sp=r_sp,
        )
    elif not isinstance(policy, Policy):
        raise TypeError(f"expected a Policy (or legacy mode str), got {policy!r}")
    elif any(v is not None for v in (target_psnr, target_ratio, eb_abs, eb_rel, r_sp)):
        raise ValueError("pass either policy= or the legacy target kwargs, not both")
    fields = list(fields)
    with TraceAnnotation("repro.compress.solve", fields=len(fields)):
        return _solve_many(fields, policy, transform, rounds, cache, names)


def _solve_many(
    fields: list, policy: Policy, transform: str, rounds: int | None, cache, names
) -> list[TargetSolution]:
    """`solve_many` after its policy is resolved."""
    mode = policy.mode
    if mode == "raw":
        raise ValueError("solve_many has nothing to solve for Policy.raw()")
    if mode == "fixed_accuracy":
        sels = select_many(
            fields, policy=policy, transform=transform, cache=cache, names=names
        )
        # raw stores are lossless at exactly 32 b/v, whatever the estimates
        # said — keep the telemetry consistent with the target modes
        return [
            TargetSolution(
                s, mode, s.eb_abs,
                math.inf if s.codec == "raw" else s.psnr_target,
                RAW_BITS if s.codec == "raw" else min(s.br_sz, s.br_zfp),
                True,
            )
            for s in sels
        ]
    attr = TARGET_FIELD.get(mode)
    if attr is None:  # a future Policy mode this controller predates
        raise ValueError(
            f"solve_many cannot solve mode {mode!r}; supported target "
            f"modes: {', '.join(TARGET_FIELD)}"
        )
    target = float(getattr(policy, attr))
    n_rounds = DEFAULT_ROUNDS[mode] if rounds is None else rounds

    results: list[TargetSolution | None] = [None] * len(fields)
    groups = _build_solve_members(
        fields, range(len(fields)), results, mode, target, policy.r_sp
    )
    if cache is None:
        _solve_groups(
            groups, results, mode, target, n_rounds, policy.r_sp, transform,
            policy.codecs,
        )
        return results  # type: ignore[return-value]
    _solve_many_cached(
        fields, names, results, groups, cache, policy, mode, target, n_rounds,
        transform,
    )
    return results  # type: ignore[return-value]


def _solve_many_cached(
    fields,
    names,
    results: list[TargetSolution | None],
    groups: dict[int, list[_Member]],
    cache,
    policy: Policy,
    mode: str,
    target: float,
    n_rounds: int,
    transform: str,
) -> None:
    """Warm half of `solve_many`'s target modes (DESIGN.md §8), mirroring
    `selector._select_many_cached`: fingerprint each member against the
    cache, replay validated `TargetSolution`s, sweep only the misses.
    Misses whose entry merely drifted (key match, fingerprint mismatch)
    seed the secant from the previously solved bound when the cache opts
    into `warm_start` — the solution moved a little, so the old root is a
    better starting bracket than the model curve."""
    from . import predictor as _pred

    if names is None:
        raise ValueError("solve_many(cache=...) requires names=")
    names = list(names)
    if len(names) != len(fields):
        raise ValueError(
            f"names/fields length mismatch: {len(names)} vs {len(fields)}"
        )
    miss_groups: dict[int, list[_Member]] = {}
    warm: dict[int, tuple[float, float]] = {}
    to_store: list[tuple[int, str, tuple, str, dict]] = []
    for nd, members in groups.items():
        tuples = [(m.idx, m.blocks, 0.0, m.vr, m.size) for m in members]
        stats = _pred.stats_for_members(nd, tuples, policy.r_sp)
        for m, (_stats, fp) in zip(members, stats):
            i = m.idx
            x = fields[i]
            shape = tuple(np.shape(x))
            dtype = str(getattr(x, "dtype", np.asarray(x).dtype))
            entry = cache.lookup(names[i], shape, dtype, policy, transform, fp)
            if entry is not None and entry.solution is not None:
                results[i] = entry.to_solution()
                continue
            miss_groups.setdefault(nd, []).append(m)
            to_store.append((i, names[i], shape, dtype, fp))
            if cache.warm_start:
                prev = cache.stale(names[i], shape, dtype, policy, transform)
                if prev is not None and prev.solution is not None:
                    sel = prev.to_selection()
                    if sel.codec != "raw" and sel.eb_sz > 0:
                        x_s = math.log2(2.0 * sel.eb_sz)
                        x_z = (
                            math.log2(sel.eb_abs)
                            if sel.codec == "zfp" and sel.eb_abs > 0
                            else x_s - 1.0
                        )
                        warm[i] = (x_s, x_z)
    if miss_groups:
        _solve_groups(
            miss_groups, results, mode, target, n_rounds, policy.r_sp,
            transform, policy.codecs, warm=warm or None,
        )
    for i, name, shape, dtype, fp in to_store:
        sol = results[i]
        cache.store(
            name, shape, dtype, policy, transform, fp, sol.selection,
            solution=sol,
        )


def _build_solve_members(
    fields,
    indices,
    results: list[TargetSolution | None],
    mode: str,
    target: float,
    r_sp: float,
) -> dict[int, list[_Member]]:
    """Gather-side half of `solve_many`: fold + degenerate raw fallback
    (written straight into `results`) + monster-field sample stride-down,
    returning batchable members as nd -> [_Member]. Split out so the
    shard-local engine (DESIGN.md §6) can merge device-gathered members
    into the same batches as host-gathered ones — identical batch
    composition, hence bit-identical target solves on mixed pytrees."""
    groups: dict[int, list[_Member]] = {}
    for i, x in zip(indices, fields):
        arr = np.asarray(x, dtype=np.float32)
        view = _fold_ndim(arr)
        vr = float(np.max(view) - np.min(view)) if view.size else 0.0
        sel0 = _degenerate_selection(view, vr, None, None, r_sp)
        if sel0 is not None:
            # raw is lossless, so every quality-floor contract (PSNR and
            # the §7.4 metrics) is met by construction; only a *rate*
            # budget is genuinely missed (raw pins the ratio to 1)
            on = mode != "fixed_ratio"
            results[i] = TargetSolution(
                sel0, mode, target, math.inf, RAW_BITS, on,
                est_metric=qual.lossless_metric(mode),
            )
            continue
        starts = est.block_starts(view.shape, r_sp)
        cap = _max_batch_blocks(view.ndim)
        if len(starts) > cap:
            # monster field: stride the sample grid down to the launch cap
            # (lower effective r_sp) so it still rides the batched sweep
            starts = starts[:: -(-len(starts) // cap)]
        groups.setdefault(view.ndim, []).append(
            _Member(i, est.gather_blocks_np(view, starts, halo=True), vr, view.size)
        )
    return groups


def _solve_groups(
    groups: dict[int, list[_Member]],
    results: list[TargetSolution | None],
    mode: str,
    target: float,
    n_rounds: int,
    r_sp: float,
    transform: str,
    codecs: tuple[str, ...] = _codecs.DEFAULT_CODECS,
    warm: dict[int, tuple[float, float]] | None = None,
) -> None:
    """Drive the per-batch target solvers over pre-gathered `_Member`s.
    Shared by `solve_many` (host-gathered samples) and the shard-local
    engine (device-gathered samples, DESIGN.md §6): the solvers see the
    identical packed batches either way, so sharded target-mode decisions
    are bit-identical to the unsharded path by construction.

    `warm` maps a member index to cached (log2 SZ bin, log2 ZFP bound)
    secant seeds from an invalidated decision-cache entry (DESIGN.md §8);
    unmapped members keep the cold model seeds."""
    for nd, members in groups.items():
        cap = _max_batch_blocks(nd)
        lo = 0
        while lo < len(members):
            hi, blocks = lo, 0
            while hi < len(members) and (
                hi == lo
                or (
                    blocks + len(members[hi].blocks) <= cap
                    and hi - lo < MAX_BATCH_FIELDS
                )
            ):
                blocks += len(members[hi].blocks)
                hi += 1
            batch = members[lo:hi]
            sweep = _Sweep(nd, batch, transform)
            # refinement probes run on a strided sub-sample of the blocks
            # already in hand — the secant needs trends, not decision-grade
            # estimates; the final pricing eval uses the full sample
            refine = _Sweep(
                nd,
                [
                    _Member(m.idx, m.blocks[::REFINE_STRIDE], m.vr, m.size)
                    for m in batch
                ],
                transform,
            )
            vr_arr = np.asarray([m.vr for m in batch], np.float32)
            warm_batch = None
            if warm:
                warm_s = np.full(len(batch), np.nan)
                warm_z = np.full(len(batch), np.nan)
                for f, m in enumerate(batch):
                    if m.idx in warm:
                        warm_s[f], warm_z[f] = warm[m.idx]
                if np.isfinite(warm_s).any() or np.isfinite(warm_z).any():
                    warm_batch = (warm_s, warm_z)
            if mode in qual.MODE_METRIC:
                solved_m = _solve_fixed_metric(
                    sweep, refine, batch, nd, vr_arr, mode, target, n_rounds,
                    r_sp, codecs, warm=warm_batch,
                )
                for m, (sel, ps, br, on, met) in zip(batch, solved_m):
                    results[m.idx] = TargetSolution(
                        sel, mode, target, ps, br, on, est_metric=met
                    )
            else:
                solver = (
                    _solve_fixed_psnr if mode == "fixed_psnr" else _solve_fixed_ratio
                )
                solved = solver(
                    sweep, refine, vr_arr, target, n_rounds, r_sp, codecs,
                    warm=warm_batch,
                )
                for m, (sel, ps, br, on) in zip(batch, solved):
                    results[m.idx] = TargetSolution(sel, mode, target, ps, br, on)
            lo = hi


def solve(x, policy: Policy | str, **kw) -> TargetSolution:
    """Single-field convenience wrapper over `solve_many`."""
    return solve_many([x], policy, **kw)[0]


def estimate_curves(
    x,
    bounds,
    r_sp: float = est.DEFAULT_SAMPLING_RATE,
    transform: str = "zfp",
) -> dict[str, np.ndarray]:
    """Evaluate both estimated rate-distortion curves of one field at an
    array of bounds, in one vmapped launch (the controller's objective,
    exposed for benchmarks/tests — e.g. the monotonicity invariant the
    secant/bracket search relies on). `bounds[c]` is used as ZFP's error
    bound AND as SZ's bin size delta for candidate c. Returns arrays of
    len(bounds): ``br_sz``, ``psnr_sz``, ``br_zfp``, ``psnr_zfp``, and
    ``psnr_sz_measured`` (the sampled quantization-error PSNR the
    fixed_psnr refinement targets).
    """
    view = _fold_ndim(np.asarray(x, dtype=np.float32))
    vr = float(np.max(view) - np.min(view)) if view.size else 0.0
    if _degenerate_selection(view, vr, None, None, r_sp) is not None:
        raise ValueError("degenerate field has no estimator curve")
    starts = est.block_starts(view.shape, r_sp)
    member = _Member(0, est.gather_blocks_np(view, starts, halo=True), vr, view.size)
    sweep = _Sweep(view.ndim, [member], transform)
    b = np.asarray(bounds, np.float32).reshape(-1, 1)
    br_sz, psnr_sz, br_zfp, psnr_zfp, psnr_meas = sweep.full(b, b)
    return dict(
        br_sz=br_sz[:, 0], psnr_sz=psnr_sz[:, 0],
        br_zfp=br_zfp[:, 0], psnr_zfp=psnr_zfp[:, 0],
        psnr_sz_measured=psnr_meas[:, 0],
    )
