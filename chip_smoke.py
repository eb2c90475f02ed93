"""Drive the compressor's main paths once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: archive, train, serve
    python chip_smoke.py --chips 4    # four chips: the sharded checkpoint only

One chip, three phases, each through the entry points a user calls:

* archive — one field of each of the paper's data sets at its SDRBench
  shape (CESM-ATM 1800x3600, Hurricane-ISABEL 100x500x500, NYX 512^3),
  generated on the device from --seed, through `compress_pytree` /
  `decompress_pytree` under `Policy.fixed_accuracy(eb_rel=1e-4)`, once on
  the default path and once with `device_encode=True`. Every value must be
  within its bound, each device-encoded stream byte-identical to the host
  coder's stream over the same codes (DESIGN.md §3.7), the kernels must
  agree with `kernels/ref.py` on the chip, and the device-encode program
  must hold the compiled Lorenzo kernel (`tpu_custom_call`).
* train — `launch.train.main` for smollm-360m at its published widths,
  3 steps with a finite loss, then one compressed `CheckpointManager` save
  of the params and `restore_tree`, bound-checked pointwise.
* serve — `ContinuousBatcher` with smollm-360m at its published widths, the
  paged KV pool under `serving_policies(8.0)` and an arena small enough to
  force evictions: every request emits exactly `max_new` tokens, pages are
  evicted, and the BOT kernel compiled (`tpu_custom_call`).

`--chips 4` runs only the shard-local sharded checkpoint (DESIGN.md §6):
a save sharded over a (2, 2) mesh, restored under a (4, 1) mesh, with
decisions and bytes checked against the unsharded writer and the shard
placement printed per device.

Every phase prints its own lines. The last line of stdout is
`{"ok": true, "device": {...}}`; off a TPU, or when any check fails, the
script exits nonzero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: (shape, spectral slope, nonlinearity) per data set — the SDRBench shapes
#: with the spectra of benchmarks/common.py's suites
DATASETS = {
    "CESM-ATM": ((1800, 3600), -3.0, None),
    "Hurricane-ISABEL": ((100, 500, 500), -3.5, None),
    "NYX": ((512, 512, 512), -2.8, "exp"),
}
EB_REL = 1e-4
TRAIN_ARGS = ["--arch", "smollm-360m", "--seq", "256", "--batch", "8", "--steps", "3"]
#: serving workload: prompt lengths (short/long mix), tokens per request
SERVE_PROMPTS = (24, 200, 40, 220)
SERVE_NEW = 16

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_compile_s = [0.0]


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile_s[0] += duration


class Timer:
    """Wall seconds of a block and the compile seconds inside it."""

    def __enter__(self):
        self.c0, self.t0 = _compile_s[0], time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        self.compile = _compile_s[0] - self.c0

    def __str__(self):
        return f"{self.seconds:.2f}s (compile {self.compile:.2f}s)"


def spectral_field(shape, slope: float, key, nonlin=None) -> jax.Array:
    """Device twin of `benchmarks.common._spectral_field`: a Gaussian
    random field with a k^slope power spectrum (complex64 FFTs on the
    device), standardized, optionally log-normal ('exp')."""
    white = jax.random.normal(key, shape, jnp.float32)
    k2 = sum(
        jnp.fft.fftfreq(s, dtype=jnp.float32).reshape(
            [-1 if d == i else 1 for d in range(len(shape))]
        ) ** 2
        for i, s in enumerate(shape)
    )
    k2 = k2.at[(0,) * len(shape)].set(1e-12)
    x = jnp.real(jnp.fft.ifftn(jnp.fft.fftn(white) * k2 ** (slope / 4.0)))
    x = (x - x.mean()) / (x.std() + 1e-12)
    if nonlin == "exp":
        x = jnp.exp(x)
    return x.astype(jnp.float32)


def _check(ok: bool, what: str) -> None:
    """A smoke check that holds under `python -O` too."""
    if not ok:
        raise AssertionError(what)


def _bound(x: np.ndarray, eb: float) -> float:
    """The pointwise bound plus float32 rounding of the reconstruction."""
    return eb + 4 * float(np.spacing(np.float32(np.abs(x).max())))


def _hlo_has_kernel(fn, *args) -> bool:
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def _stream_parity(view: np.ndarray, cf) -> tuple[bool, bool]:
    """(declined, identical): whether the device tier declined the field
    (its stream is the host coder's), else whether the device stream
    equals the host coder's stream over the device's own codes."""
    from repro.core import device_encode as de
    from repro.core import sz, zfp

    sel = cf.selection
    if cf.codec == "sz":
        if cf.data[:4] != sz.DEVICE_MAGIC:
            return True, True
        d = de.sz_device_residuals(view, sel.eb_sz)
        delta = float(np.float32(2.0) * np.float32(sel.eb_sz))
        return False, cf.data == sz.sz_encode_residuals(
            d, view.shape, delta, magic=sz.DEVICE_MAGIC
        )
    if cf.codec == "zfp":
        if de.zfp_encode_device(view, sel.eb_abs) is None:
            return True, True
        q, e = de.zfp_device_codes(view, sel.eb_abs)
        padded = tuple(s + (-s) % 4 for s in view.shape)
        return False, cf.data == zfp.zfp_encode_quantized(
            q, e, view.shape, padded, sel.eb_abs
        )
    return True, True  # raw: no device stream


#: kernel parity fields: several tiles per axis, and the z-carry across
#: 3-D Lorenzo tiles
PARITY_SHAPES = ((600, 3000), (40, 64, 1000))


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def kernel_parity(seed: int, shapes=PARITY_SHAPES) -> None:
    """The kernels against `kernels/ref.py` on small fields."""
    from repro.kernels import ops, ref

    key = jax.random.key(seed)
    for shape in shapes:
        x = spectral_field(shape, -2.5, key)
        eb = 1e-3 * float(x.max() - x.min())
        got = np.asarray(ops.lorenzo_encode(x, eb))
        want = np.asarray(ref.lorenzo_encode_ref(x, eb))
        rec, bits = ops.bot_fused(x, eb)
        pad = tuple((0, (-s) % 4) for s in shape)
        fused_ref = ref.bot2d_fused_ref if len(shape) == 2 else ref.bot3d_fused_ref
        rec_ref, bits_ref = fused_ref(jnp.pad(x, pad), eb)
        bits_ref = np.asarray(bits_ref)[tuple(slice(0, -(-s // 4)) for s in shape)]
        err = float(jnp.max(jnp.abs(rec - x)))
        rec_ref = np.asarray(rec_ref)[tuple(slice(0, s) for s in shape)]
        rec_gap = float(jnp.max(jnp.abs(rec - rec_ref)))
        bits_diff = float(np.mean(np.asarray(bits) != bits_ref))
        print(f"[kernels] {shape}: lorenzo codes mismatched {int(np.sum(got != want))}/"
              f"{got.size}; bot max err/eb {err / eb:.4f}, recon gap to ref "
              f"{rec_gap / eb:.2e} eb, bits blocks differing {bits_diff:.2e}", flush=True)
        _check(np.array_equal(got, want), "Lorenzo kernel codes differ from ref.py")
        _check(err <= eb, "BOT kernel reconstruction outside its bound")
        _check(bits_diff < 1e-3, "BOT kernel bits differ from ref.py")


def archive_phase(datasets: dict = DATASETS, seed: int = 0, eb_rel: float = EB_REL,
                  parity_shapes=PARITY_SHAPES) -> list:
    """One field per data set through the default and device-encode paths."""
    from repro.core import Policy, compress_pytree, decompress_pytree
    from repro.core import device_encode as de
    from repro.core.selector import _fold_ndim

    kernel_parity(seed, parity_shapes)
    pol = Policy.fixed_accuracy(eb_rel=eb_rel)
    out = []
    for i, (name, (shape, slope, nonlin)) in enumerate(datasets.items()):
        with Timer() as t_gen:
            x = np.asarray(spectral_field(shape, slope, jax.random.key(seed + i), nonlin))
        row = {"dataset": name, "shape": shape, "bytes": x.nbytes}
        print(f"[archive] {name} {shape} f32 {x.nbytes / 1e6:.1f} MB generated in {t_gen}",
              flush=True)
        for path, dev in (("default", False), ("device_encode", True)):
            with Timer() as t_c:
                ct = compress_pytree({name: x}, pol, device_encode=dev)
            with Timer() as t_d:
                back = decompress_pytree(ct)[name]
            cf = ct.fields[name]
            eb = cf.selection.eb_abs if cf.selection is not None else 0.0
            err = float(np.abs(back - x).max())
            ok = err <= _bound(x, eb)
            line = (f"[archive] {name} {path}: codec {cf.codec} ratio {ct.ratio:.3f} "
                    f"max err/bound {err / max(eb, 1e-30):.4f} compress {t_c} "
                    f"decompress {t_d}")
            if dev:
                view = _fold_ndim(x)
                declined, same = _stream_parity(view, cf)
                line += f" device declined {declined} stream parity {same}"
                _check(same, f"{name}: device stream differs from the host coder's")
                kernel = _hlo_has_kernel(
                    de._sz_pass1, jax.ShapeDtypeStruct(view.shape, jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.float32),
                )
                line += f" lorenzo tpu_custom_call {kernel}"
                row["lorenzo_kernel"] = kernel
                # compiled on the chip; interpreted (plain HLO) on CPU
                _check(kernel == _on_tpu(), f"{name}: Lorenzo kernel compiled {kernel}")
            print(line, flush=True)
            _check(ok, f"{name} {path}: max error {err} beyond bound {eb}")
            row[path] = dict(codec=cf.codec, ratio=ct.ratio, err_over_eb=err / max(eb, 1e-30),
                             compress_s=t_c.seconds, decompress_s=t_d.seconds)
        out.append(row)
    return out


def train_phase(train_args=TRAIN_ARGS, eb_rel: float = EB_REL) -> dict:
    """`launch.train.main`, then one compressed checkpoint save + restore of
    the params (the AdamW moments would triple the host coding time)."""
    from repro.checkpoint import CheckpointConfig, CheckpointManager
    from repro.core import Policy
    from repro.launch import train

    with Timer() as t_train:
        res = train.main(list(train_args))
    losses = res["losses"]
    print(f"[train] {' '.join(train_args)}: losses {[round(v, 4) for v in losses]} "
          f"in {t_train}", flush=True)
    _check(bool(losses) and all(np.isfinite(losses)), "non-finite training loss")
    state = {"params": res["params"]}
    n_values = sum(int(np.prod(np.shape(v))) for v in jax.tree_util.tree_leaves(state))
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(
            CheckpointConfig(d, policy=Policy.fixed_accuracy(eb_rel=eb_rel))
        )
        with Timer() as t_save:
            path = mgr.save(len(losses), state)
        with open(os.path.join(path, "manifest.json")) as f:
            man = json.load(f)
        with Timer() as t_restore:
            _, back = mgr.restore_tree(state)
    worst = 0.0
    for p0, p1 in zip(jax.tree_util.tree_leaves(state["params"]),
                      jax.tree_util.tree_leaves(back["params"])):
        p0 = np.asarray(p0, np.float32)
        if p0.size < 64:
            continue
        eb = eb_rel * float(p0.max() - p0.min())
        err = float(np.abs(np.asarray(p1, np.float32) - p0).max())
        worst = max(worst, err / _bound(p0, eb))
    codecs = {}
    for fl in man["fields"]:
        codecs[fl["codec"]] = codecs.get(fl["codec"], 0) + 1
    print(f"[train] checkpoint of the params: "
          f"{n_values * 4 / 1e9:.3f} GB f32 -> {man['total_bytes'] / 1e9:.3f} GB "
          f"({man['raw_bytes'] / max(man['total_bytes'], 1):.3f}x), codecs {codecs}, "
          f"save {t_save}, restore {t_restore}, params max err/bound {worst:.4f}",
          flush=True)
    _check(worst <= 1.0, "restored params outside their bound")
    return dict(losses=losses, train_s=t_train.seconds, save_s=t_save.seconds,
                restore_s=t_restore.seconds, gb=n_values * 4 / 1e9)


def serve_phase(arch: str = "smollm-360m", smoke: bool = False,
                prompts=SERVE_PROMPTS, max_new: int = SERVE_NEW, seed: int = 0) -> dict:
    """Paged continuous batching with compress-on-evict under page pressure."""
    from repro.configs import get_config
    from repro.core.decision_cache import DecisionCache
    from repro.core.policy import serving_policies
    from repro.kernels import ops
    from repro.models import build_model, reduced_for_smoke
    from repro.models import nn as rnn
    from repro.runtime.batcher import ContinuousBatcher, Request

    cfg = get_config(arch)
    if smoke:
        cfg = reduced_for_smoke(cfg)
    model = build_model(cfg)
    params = rnn.init_tree(model.desc(), jax.random.key(seed))
    page_tokens = 16
    max_len = max(prompts) + max_new
    max_pages = -(-max_len // page_tokens)
    lossy = DecisionCache()  # one entry per page evicted under fixed_ratio
    b = ContinuousBatcher(
        model, params, slots=2, max_len=max_len, eos_id=-1,
        page_tokens=page_tokens, arena_pages=max_pages,
        policies=serving_policies(8.0), long_threshold=128, decisions=lossy,
    )
    rng = np.random.default_rng(seed)
    reqs = [
        Request(rid=i, prompt=rng.integers(1, cfg.vocab, n).astype(np.int32), max_new=max_new)
        for i, n in enumerate(prompts)
    ]
    with Timer() as t:
        b.run(reqs)
    counts = [len(r.out) for r in reqs]
    page = (cfg.n_layers, page_tokens, cfg.n_kv_heads * cfg.dh)
    kernel = _hlo_has_kernel(
        lambda p: ops.bot_fused(p, 1e-3), jax.ShapeDtypeStruct(page, jnp.float32)
    )
    print(f"[serve] {arch} d_model {cfg.d_model} x {cfg.n_layers} layers, prompts "
          f"{list(prompts)}, max_new {max_new}, arena {max_pages} pages of "
          f"{page_tokens} tokens: tokens per request {counts}, evictions "
          f"{b.stats['evictions']}, restores {b.stats['restores']}, pages compressed "
          f"at fixed_ratio(8) {len(lossy.entries)}, page {page} "
          f"bot tpu_custom_call {kernel}, {t}", flush=True)
    _check(all(c == max_new for c in counts), "a request did not emit max_new tokens")
    _check(b.stats["evictions"] > 0, "no page was evicted")
    _check(bool(lossy.entries), "no page was compressed by the BOT path")
    _check(kernel == _on_tpu(), f"BOT kernel compiled {kernel}")
    return dict(tokens=counts, evictions=b.stats["evictions"], bot_kernel=kernel,
                seconds=t.seconds)


def sharded_phase(n_fields: int = 8, dim: int = 2048, eb_rel: float = 1e-3) -> dict:
    """Sharded save over a (2, 2) mesh, elastic restore under (4, 1), parity
    against the unsharded writer (`launch.shardckpt.dryrun`)."""
    from jax.sharding import AxisType, Mesh

    from repro.launch.shardckpt import dryrun

    devs = np.array(jax.devices()[:4])
    axes = ("data", "model")
    auto = (AxisType.Auto, AxisType.Auto)
    mesh = Mesh(devs.reshape(2, 2), axes, axis_types=auto)
    mesh2 = Mesh(devs.reshape(4, 1), axes, axis_types=auto)
    with tempfile.TemporaryDirectory() as d, Timer() as t:
        res = dryrun(mesh, mesh2, n_fields, dim, eb_rel, d)
    for when in ("saved_on", "restored_on"):
        print(f"[sharded] {when}: {res[when]}", flush=True)
        _check(len(res[when]) == 4, f"shards {when} {len(res[when])} devices, not 4")
    print(f"[sharded] {n_fields} fields of {dim}x{dim} (params + moments) in {t}", flush=True)
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev[0].platform!r}", file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(dev)} devices", file=sys.stderr)
        return 2
    from repro.launch.cache import cache_stats, use_compile_cache

    use_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"device: {dev[0].platform} {dev[0].device_kind} x{len(dev)}", flush=True)
    phases = (
        [("sharded", sharded_phase)] if args.chips == 4
        else [("archive", lambda: archive_phase(seed=args.seed)),
              ("train", train_phase),
              ("serve", lambda: serve_phase(seed=args.seed))]
    )
    for name, fn in phases:
        try:
            with Timer() as t:
                fn()
        except BaseException:  # noqa: BLE001 - report, then fail the run
            traceback.print_exc()
            print(f"[{name}] FAILED", flush=True)
            return 1
        print(f"[{name}] passed in {t}", flush=True)
    print(f"compile cache: {cache_stats()}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind, "count": len(dev),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
