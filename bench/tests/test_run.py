"""A whole run on the CPU (the harness's look for a chip skipped), and the
refusal of a device that `bench/peaks.json` does not list."""

from conftest import TINY, run_cell


def test_untraced_run_prints_the_end_to_end_metrics(tiny_root, capsys):
    rc, res = run_cell(tiny_root, capsys, "--workload", TINY, "--seed", str(2**31 + 7),
                       "--seconds", "0.5", "--trace", "0")
    assert rc == 0 and res["correct"], res
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"compress_GBps", "decompress_GBps", "ratio", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["err_over_bound"]["value"] <= 1.0


def test_traced_run_prints_the_per_layer_metrics(tiny_root, capsys):
    rc, res = run_cell(tiny_root, capsys, "--workload", TINY, "--seed", "11",
                       "--seconds", "0.5", "--trace", "1")
    assert rc == 0 and res["correct"], res
    # the CPU has no device plane: no idle share can be read there
    assert set(res["metrics"]) == {"compress.select_pct", "host_encode.s_per_GB",
                                   "host_decode.s_per_GB", "host_decode.cpu_s_per_GB"}
    assert res["metrics"]["host_decode.cpu_s_per_GB"]["value"] > 0
    assert 0 < res["metrics"]["compress.select_pct"]["value"] < 100
    assert res["device"]["window_s"] > 0
    assert {"device_ops", "idle_gaps"} == set(res["breakdown"])


def test_same_seed_same_snapshot():
    import numpy as np

    from bench import cells, data

    cell = cells.load("hurricane-isabel.eb1e-4")
    a = data.snapshot((8, 16, 24), cell.fields[:2], 2**33 + 1)
    b = data.snapshot((8, 16, 24), cell.fields[:2], 2**33 + 1)
    c = data.snapshot((8, 16, 24), cell.fields[:2], 1)  # the same low 32 bits
    for k in a:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]))
        assert not np.array_equal(np.asarray(a[k]), np.asarray(c[k]))


def test_seeds_share_the_large_scales():
    """Below `fixed_below` every seed has the same phases; above it, its own."""
    import numpy as np

    from bench import cells, data

    shape, below = (8, 32, 32), 0.1
    k2 = sum(np.fft.fftfreq(s).reshape([-1 if d == i else 1 for d in range(3)]) ** 2
             for i, s in enumerate(shape))
    low, high = (k2 > 0) & (k2 < below**2), k2 >= below**2
    assert low.sum() > 0
    fields = cells.load("hurricane-isabel.eb1e-4").fields[1:2]  # no nonlinearity
    a, b = (np.fft.fftn(np.asarray(next(iter(data.snapshot(shape, fields, s, below).values())),
                                   np.float64)) for s in (2**40 + 3, 5))
    assert np.allclose(a[low], b[low], rtol=1e-3, atol=1e-3 * np.abs(a[low]).max())
    assert np.abs(a[high] - b[high]).mean() > 0.5 * np.abs(a[high]).mean()


def test_refuses_a_device_without_peaks(tiny_root, capsys):
    from bench import run

    rc = run.main(["--workload", TINY, "--seed", "1", "--seconds", "1", "--trace", "0"],
                  root=tiny_root)
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no peaks for device kind" in out.err
    assert not any(line.startswith("{") for line in out.err.splitlines())
