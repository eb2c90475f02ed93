"""The plain reference that decides `correct`, and the control.

The configuration states the guarantee: every value of every field comes
back within the pointwise bound `eb_rel * (max - min)` of the field (or
`eb_abs`). The reference computes that bound in float64 from the field
the benchmark generated, and compares the program's output with it:

* `bound_gap`: the largest relative gap between the bound Stage I-II
  solved (`Selection.eb_abs`) and the reference's bound;
* `err_over_bound`: the largest pointwise error of a reconstruction,
  decoded from the streams alone, over the reference's bound plus the
  float32 rounding of the output.

A field that is missing, misshapen or not finite reads `inf`. Nothing
here imports the program.

The control is `QuantizeReference`: a plain error-bounded compressor
(uniform quantization at step 2 * eb, the bound from the field's range)
put in the program's place. In float32 it meets the guarantee; in
bfloat16, the step below the configuration's float32, it must fail.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench.system import Stream

#: limit of each number compared; PERF.md gives the readings they are set from
LIMITS = {"bound_gap": 1e-5, "err_over_bound": 1.0}


def reference_bound(x: np.ndarray, policy_spec: dict) -> float:
    if policy_spec.get("eb_abs") is not None:
        return float(policy_spec["eb_abs"])
    x64 = np.asarray(x, np.float64)
    return float(policy_spec["eb_rel"]) * float(x64.max() - x64.min())


def _rounding(x: np.ndarray) -> float:
    """Float32 rounding of an output value as large as the field's largest."""
    return 4.0 * float(np.spacing(np.float32(np.abs(x).max())))


def numbers(originals: dict, policy_spec: dict, requests: list) -> dict[str, float]:
    """The compared numbers over every request of a run.

    `requests` holds (streams, reconstruction) pairs; `originals` the
    fields as generated, on the host."""
    bound_gap, err_over = 0.0, 0.0
    for name, x in originals.items():
        x64 = np.asarray(x, np.float64)
        eb = reference_bound(x64, policy_spec)
        slack = _rounding(x)
        for streams, recon in requests:
            solved = [s.bound for s in streams if s.name == name]
            if len(solved) != 1:
                gap = math.inf
            elif eb > 0:
                gap = abs(solved[0] - eb) / eb
            else:  # a constant field: only a zero bound matches
                gap = 0.0 if solved[0] == eb else math.inf
            bound_gap = max(bound_gap, gap if math.isfinite(gap) else math.inf)
            r = recon.get(name)
            if r is None or tuple(np.shape(r)) != x64.shape:
                err_over = math.inf
                continue
            err = float(np.max(np.abs(np.asarray(r, np.float64) - x64)))
            err_over = max(err_over, err / (eb + slack) if math.isfinite(err) else math.inf)
    return {"bound_gap": bound_gap, "err_over_bound": err_over}


def within(nums: dict[str, float]) -> bool:
    return all(nums[k] <= lim for k, lim in LIMITS.items())


def check_lines(nums: dict[str, float]) -> dict[str, dict]:
    """{name: {"value", "limit"}} in LIMITS order, for the result line; a
    number that is not finite reads as the largest float, so the line stays
    plain JSON."""
    return {k: {"value": min(nums[k], sys.float_info.max), "limit": lim}
            for k, lim in LIMITS.items()}


class QuantizeReference:
    """Uniform quantization at step 2 * eb, every step in `dtype`.

    The bound is `eb_rel` times the field's range as `dtype` holds it; the
    codes are `round(x / (2 * eb))` and the reconstruction `codes * 2 * eb`.
    """

    def __init__(self, policy_spec: dict, dtype=jnp.bfloat16):
        self.spec = policy_spec
        self.dtype = jnp.dtype(dtype)
        self._enc = jax.jit(self._encode)
        self._dec = jax.jit(self._decode)

    def _encode(self, x):
        xd = x.astype(self.dtype)
        if self.spec.get("eb_abs") is not None:
            eb = jnp.asarray(self.spec["eb_abs"], self.dtype)
        else:
            eb = (jnp.asarray(self.spec["eb_rel"], self.dtype) * (xd.max() - xd.min()))
        eb = eb.astype(self.dtype)
        return jnp.round(xd / (2 * eb)).astype(self.dtype), eb

    def _decode(self, q, eb):
        return (q * (2 * eb)).astype(self.dtype).astype(jnp.float32)

    def compress(self, snapshot: dict) -> list[Stream]:
        out = []
        for name, x in snapshot.items():
            q, eb = self._enc(x)
            data = np.asarray(q).tobytes()
            out.append(Stream(name, f"quantize.{self.dtype.name}", data, tuple(x.shape),
                              "float32", float(eb)))
        return out

    def decompress(self, streams: list[Stream]) -> dict:
        out = {}
        for s in streams:
            q = jnp.asarray(np.frombuffer(s.data, self.dtype).reshape(s.shape))
            out[s.name] = np.asarray(self._dec(q, jnp.asarray(s.bound, self.dtype)))
        return out
