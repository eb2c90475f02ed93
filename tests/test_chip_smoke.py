"""CPU rehearsal of `chip_smoke.py`: every phase function at tiny shapes,
with the Pallas kernels interpreted. The script itself refuses to run
anywhere but on a TPU."""

import numpy as np
import pytest

import chip_smoke


def test_main_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 2
    out, err = capsys.readouterr()
    assert "needs a TPU" in err and '"ok"' not in out


def test_archive_phase_tiny():
    datasets = {
        "CESM-ATM": ((48, 96), -3.0, None),
        "Hurricane-ISABEL": ((8, 24, 40), -3.5, None),
        "NYX": ((16, 16, 16), -2.8, "exp"),
    }
    rows = chip_smoke.archive_phase(
        datasets, seed=1, parity_shapes=((40, 300), (12, 16, 136))
    )
    assert [r["dataset"] for r in rows] == list(datasets)
    for r in rows:
        for path in ("default", "device_encode"):
            assert r[path]["err_over_eb"] <= 1.0 + 1e-6
        assert r["lorenzo_kernel"] is False  # interpreted on CPU


def test_train_phase_tiny():
    res = chip_smoke.train_phase(
        ["--arch", "smollm-360m", "--smoke", "--seq", "32", "--batch", "2", "--steps", "3"]
    )
    assert len(res["losses"]) == 3 and np.all(np.isfinite(res["losses"]))


def test_serve_phase_tiny():
    res = chip_smoke.serve_phase(smoke=True)
    assert res["tokens"] == [chip_smoke.SERVE_NEW] * len(chip_smoke.SERVE_PROMPTS)
    assert res["evictions"] > 0


@pytest.mark.multidevice
def test_sharded_phase_tiny(emulated_devices):
    res = chip_smoke.sharded_phase(n_fields=2, dim=64)
    assert len(res["saved_on"]) == 4 and len(res["restored_on"]) == 4
