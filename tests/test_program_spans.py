"""The program's spans inside `compress_pytree`, `decompress_pytree` and the
host coders, recorded by the JAX profiler on CPU and reduced the way the
benchmark reduces a chip trace (`bench/tracing.py`, `bench/program_spans.py`).

Also: the names of the Stage I-II device programs, which a trace's
"XLA Modules" line shows."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program_spans, tracing
from repro.core import api, controller, estimator, predictor, selector
from repro.core.policy import Policy

POLICY = Policy.fixed_accuracy(eb_rel=1e-4)
SHAPE = (16, 32, 32)


def _tree():
    rng = np.random.default_rng(7)
    smooth = np.cumsum(np.cumsum(rng.standard_normal(SHAPE), 0), 1)
    g = np.meshgrid(*[np.linspace(0, 1, n) for n in SHAPE], indexing="ij")
    waves = np.sin(40 * g[1]) * np.cos(40 * g[2]) + 0.3 * rng.standard_normal(SHAPE)
    return {"smooth": smooth.astype(np.float32), "waves": waves.astype(np.float32),
            "flat": np.ones((8, 8, 8), np.float32)}


def _profiled(fn, tmp_path):
    """(fn(), the reduced trace of the call)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    return out, program_spans.reduce_xplane(path)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tree = _tree()
    untraced = api.compress_pytree(tree, POLICY)  # also compiles the estimator

    def request():
        ct = api.compress_pytree(tree, POLICY)
        return ct, api.decompress_pytree(ct)

    (ct, restored), trace = _profiled(request, tmp_path_factory.mktemp("trace"))
    return tree, untraced, ct, restored, trace


def test_tree_covers_every_codec(traced):
    _, _, ct, _, _ = traced
    assert ct.selection_bits == {"smooth": "sz", "waves": "zfp", "flat": "raw"}


def test_streams_identical_to_an_untraced_call(traced):
    _, untraced, ct, _, _ = traced
    for name, cf in ct.fields.items():
        assert cf.codec == untraced.fields[name].codec
        assert cf.data == untraced.fields[name].data


def test_field_spans_carry_their_args(traced):
    tree, _, ct, _, trace = traced
    (_, _, comp), = program_spans.program_spans(trace, "repro.compress_pytree")
    (_, _, decomp), = program_spans.program_spans(trace, "repro.decompress_pytree")
    assert comp == {"request": comp["request"], "fields": 3,
                    "raw_bytes": sum(x.nbytes for x in tree.values())}
    assert decomp == {"request": decomp["request"], "fields": 3}
    assert decomp["request"] != comp["request"]
    for half, req in (("encode", comp["request"]), ("decode", decomp["request"])):
        spans = program_spans.program_spans(trace, f"repro.{half}")
        args = {a["field"]: a for _, _, a in spans}
        assert set(args) == set(tree)
        for name, a in args.items():
            want = {"request": req, "field": name, "codec": ct.fields[name].codec,
                    "raw_bytes": tree[name].nbytes}
            if half == "encode":
                want["tier"] = "host"  # the CPU backend keeps Stage III on the host
            assert a == want


def test_selection_spans_inside_the_request(traced):
    _, _, _, _, trace = traced
    (a, b, _), = program_spans.program_spans(trace, "repro.compress_pytree")
    for name, args in (("repro.compress.materialize", {"fields": 3, "bytes": 133120}),
                       ("repro.compress.gather", {"fields": 3, "blocks": 32}),
                       ("repro.compress.estimate", {"fields": 2, "n_blocks": 32})):
        (s, e, got), = program_spans.program_spans(trace, name)
        assert a <= s and e <= b
        assert got == args


def test_coder_spans_inside_their_field_span(traced):
    _, _, _, _, trace = traced
    rows = trace["program_spans"]
    fields = [r for r in rows if r[0] in ("repro.encode", "repro.decode")]
    coder = [r for r in rows if r[0].startswith(("repro.sz.", "repro.zfp."))]
    assert {r[0] for r in coder} == {
        "repro.sz.quantize", "repro.sz.table", "repro.sz.pack", "repro.sz.container",
        "repro.sz.unpack", "repro.sz.reconstruct", "repro.zfp.blockize",
        "repro.zfp.quantize", "repro.zfp.planes", "repro.zfp.read_planes",
        "repro.zfp.inverse", "repro.zfp.unblockize"}
    for name, s, d, thread, _ in coder:
        assert any(t == thread and fs <= s and s + d <= fs + fd
                   for _, fs, fd, t, _ in fields), name


def test_program_spans_stay_out_of_the_harness_spans(traced):
    _, _, _, _, trace = traced
    assert trace["spans"] == []
    for name, *_ in trace["program_spans"]:
        assert name.startswith("repro.")
        assert not tracing._is_span(name)
    # one SZ, one ZFP and one raw field: 23 spans (3 SZ + 1 ZFP open 37)
    assert len(trace["program_spans"]) == 23


def test_device_tier_spans_nest_in_their_field(tmp_path):
    """Forced onto the device tier, every lossy field's `repro.encode`
    reads `tier="device"` and holds its codec's `repro.device.*` pass
    spans on the same thread; the raw field stays on the host."""
    tree = _tree()
    _, trace = _profiled(
        lambda: api.compress_pytree(tree, POLICY, device_encode=True), tmp_path)
    rows = trace["program_spans"]
    fields = [r for r in rows if r[0] == "repro.encode"]
    assert {r[4]["field"]: r[4]["tier"] for r in fields} == {
        "smooth": "device", "waves": "device", "flat": "host"}
    device = [r for r in rows if r[0].startswith("repro.device.")]
    assert {r[0] for r in device} == {
        "repro.device.sz.pass1", "repro.device.sz.table", "repro.device.sz.pass2",
        "repro.device.zfp.pass1", "repro.device.zfp.pass2a", "repro.device.zfp.pass2b"}
    for name, s, d, thread, _ in device:
        codec = name.split(".")[2]
        assert any(t == thread and a["codec"] == codec and fs <= s and s + d <= fs + fd
                   for _, fs, fd, t, a in fields), name
    assert not [r for r in rows if r[0].startswith("repro.fallback.")]


def test_restored_fields_within_bound(traced):
    tree, _, ct, restored, _ = traced
    for name, x in tree.items():
        bound = ct.fields[name].selection.eb_abs if ct.fields[name].selection else 0.0
        assert np.abs(restored[name] - x).max() <= bound * (1 + 1e-6)


def test_fallback_spans(tmp_path):
    """The device SZ coder declines a bound this tight, the host coder
    then writes more than raw, and the safety net stores raw: each step
    opens its `repro.fallback.*` span."""
    x = np.random.default_rng(0).standard_normal((16, 16, 16)).astype(np.float32)
    sel = selector.Selection("sz", 1e-12, 1e-12, 8.0, 9.0, 100.0, 8.0, 0.05)
    cf, trace = _profiled(
        lambda: selector.encode_with_selection(x, sel, device_encode=True), tmp_path)
    assert cf.codec == "raw"
    names = [r[0] for r in trace["program_spans"]]
    assert names.count("repro.fallback.device_declined") == 1
    assert names.count("repro.fallback.stream_not_smaller") == 1


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _lower_select_estimate_batched():
    return selector._batched_estimates_jitted(3, 64, 2, "zfp").lower(
        _f32(64, 5, 5, 5), _i32(64), _i32(3), _f32(2), _f32(2), _f32(2))


def _lower_select_estimate():
    starts = estimator.block_starts(SHAPE, 0.05)
    return selector._estimates_jitted(SHAPE, starts.shape, "zfp").lower(
        _f32(*SHAPE), jnp.asarray(starts), jnp.float32(1e-3), jnp.float32(1.0))


def _lower_solve_sweep():
    return controller._sweep_jitted(3, 64, 2, 4, "zfp", "full").lower(
        _f32(64, 5, 5, 5), _i32(64), _i32(3), _f32(4, 2), _f32(4, 2), _f32(2), _f32(2))


def _lower_predictor_moments():
    return predictor._moments_jitted(3, 64, 2).lower(
        _f32(64, 5, 5, 5), _i32(64), _i32(3), _f32(2))


@pytest.mark.parametrize("name,lower", [
    ("select_estimate_batched", _lower_select_estimate_batched),
    ("select_estimate", _lower_select_estimate),
    ("solve_sweep", _lower_solve_sweep),
    ("predictor_moments", _lower_predictor_moments),
])
def test_device_programs_have_stable_names(name, lower):
    assert f"module @jit_{name} " in lower().as_text()
