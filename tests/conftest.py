"""Test-session plumbing: multi-device emulation + golden-file options.

Multi-device emulation (the DESIGN.md §6 test harness): XLA only reads
`--xla_force_host_platform_device_count` when the backend initializes, so
the flag must be in the environment BEFORE anything imports jax. pytest
imports conftest.py before collecting any test module, which makes this
top-level assignment the "early-import" pattern: every test in the suite
sees 8 emulated CPU devices on a bare single-CPU CI runner, and sharded
tests (`tests/test_sharded_compress.py`, the multi-device cases in
`tests/test_sharding.py`) run for real instead of skipping. If jax was
somehow initialized first (e.g. a plugin imported it), the
`emulated_devices` fixture skips those tests instead of failing them.
"""

import os
import sys
from pathlib import Path

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# the suite runs on CPU (kernels interpreted) and never claims a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# benchmarks.common is the canonical ATM/Hurricane-like field generator the
# golden suite freezes; make it importable when pytest is launched from
# anywhere (the repo root is not otherwise guaranteed on sys.path)
_ROOT = str(Path(__file__).resolve().parent.parent)
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--update-golden",
        action="store_true",
        default=False,
        help="rewrite tests/golden/*.json from the current estimators "
        "(tests/test_golden_decisions.py) instead of comparing against them",
    )


@pytest.fixture(scope="session")
def emulated_devices():
    """Session-scoped gate for tests that need the 8 emulated devices."""
    import jax

    if jax.device_count() < 8:
        pytest.skip(
            "needs 8 emulated devices — jax initialized before conftest set "
            "XLA_FLAGS (run via pytest, not with a preloaded jax)"
        )
    return jax.devices()


@pytest.fixture(scope="session")
def update_golden(request):
    return request.config.getoption("--update-golden")
