"""Multi-process job runner for the emulated multi-host harness.

The §6.2 test story needs REAL `jax.process_count() > 1` jobs, which a
single pytest process cannot host (one jax runtime per process). This
module spawns N python workers, each joining one distributed CPU job via
`repro.runtime.dist.initialize` (gloo collectives + per-process emulated
devices), runs a named scenario in every worker, and collects per-host
results/exit codes — the machinery behind `tests/multihost/`, the
`launch/shardckpt.py --processes` dryrun, and the bench-gate parity
smoke.

Protocol (shared filesystem, no sockets beyond jax's own coordinator):

* the runner picks a free coordinator port, writes one `spec.json`
  (coordinator address, process count, per-process device count,
  scenario name + args, output dir), and launches `cmd + [spec.json]`
  once per process with `MHRUN_PROCESS_ID=<pid>` in the environment
  (XLA_FLAGS is scrubbed so the parent's emulated-device setting cannot
  leak into workers — `worker_init` re-derives it from the spec — and
  JAX_PLATFORMS=cpu keeps every worker off any accelerator);
* each worker calls `worker_init(spec_path)` FIRST (before any jax
  device use), runs its scenario, and reports through
  `write_result(...)` -> `result.<pid>.json`; uncaught scenario
  exceptions become `{"error": ...}` results with a nonzero exit;
* the runner enforces a wall-clock deadline (straggler/fault tests rely
  on workers dying or timing out) and returns one `HostResult` per
  process: exit code, captured output, parsed result payload (None when
  the worker died before reporting — exactly what the fault-injection
  assertions look for).
"""

from __future__ import annotations

import dataclasses
import json
import os
import socket
import subprocess
import sys
import tempfile
import time
from typing import Any, Sequence


def free_port() -> int:
    """An OS-assigned free TCP port for the jax coordinator."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return int(s.getsockname()[1])


@dataclasses.dataclass
class HostResult:
    """One worker's outcome: exit code, captured stdout+stderr, and the
    payload it reported (None if it died before `write_result`)."""

    process_id: int
    returncode: int
    output: str
    result: dict | None

    @property
    def ok(self) -> bool:
        return self.returncode == 0 and self.result is not None and (
            "error" not in self.result
        )


def run(
    cmd: Sequence[str],
    num_processes: int,
    *,
    scenario: str,
    args: dict | None = None,
    local_devices: int = 2,
    timeout_s: float = 600.0,
    workdir: str | None = None,
    extra_env: dict[str, str] | None = None,
) -> list[HostResult]:
    """Launch `num_processes` workers of `cmd` as one distributed job.

    `cmd` is the worker program (e.g. ``[sys.executable, worker_py]``);
    the spec path is appended as its last argument. Workers that outlive
    `timeout_s` are killed (-9) — a hung barrier in a worker must fail
    the TEST, not the suite."""
    wd = workdir or tempfile.mkdtemp(prefix="mhrun_")
    os.makedirs(wd, exist_ok=True)
    spec = dict(
        coordinator=f"127.0.0.1:{free_port()}",
        num_processes=int(num_processes),
        local_devices=int(local_devices),
        scenario=scenario,
        args=dict(args or {}),
        outdir=wd,
    )
    spec_path = os.path.join(wd, "spec.json")
    with open(spec_path, "w") as f:
        json.dump(spec, f, indent=1)

    procs: list[tuple[int, subprocess.Popen, Any]] = []
    for pid in range(num_processes):
        env = os.environ.copy()
        # the parent's emulated-device flags must not leak: each worker
        # derives its own --xla_force_host_platform_device_count from the
        # spec (worker_init), BEFORE its jax backend initializes. Workers
        # are CPU processes: on a machine with a chip, the chip stays with
        # the one process that owns it
        env.pop("XLA_FLAGS", None)
        env["JAX_PLATFORMS"] = "cpu"
        env["MHRUN_PROCESS_ID"] = str(pid)
        if extra_env:
            env.update(extra_env)
        log = open(os.path.join(wd, f"out.{pid}.log"), "w+")
        p = subprocess.Popen(
            list(cmd) + [spec_path], env=env, stdout=log, stderr=subprocess.STDOUT
        )
        procs.append((pid, p, log))

    deadline = time.monotonic() + timeout_s
    for pid, p, _ in procs:
        left = deadline - time.monotonic()
        try:
            p.wait(timeout=max(left, 0.1))
        except subprocess.TimeoutExpired:
            pass
    for pid, p, _ in procs:
        if p.poll() is None:
            p.kill()
            p.wait()

    results: list[HostResult] = []
    for pid, p, log in procs:
        log.seek(0)
        output = log.read()
        log.close()
        payload = None
        rpath = os.path.join(wd, f"result.{pid}.json")
        if os.path.exists(rpath):
            try:
                with open(rpath) as f:
                    payload = json.load(f)
            except (OSError, ValueError):
                payload = None
        results.append(HostResult(pid, int(p.returncode), output, payload))
    return results


def require_success(results: list[HostResult]) -> list[dict]:
    """All-hosts-ok assertion helper: returns the per-host payloads (by
    process id) or raises with every failed host's captured output."""
    bad = [r for r in results if not r.ok]
    if bad:
        msgs = []
        for r in bad:
            err = (r.result or {}).get("error", "<no result file>")
            msgs.append(
                f"--- host {r.process_id} exit={r.returncode} error={err}\n"
                f"{r.output[-4000:]}"
            )
        raise AssertionError(
            f"{len(bad)}/{len(results)} hosts failed:\n" + "\n".join(msgs)
        )
    return [r.result for r in sorted(results, key=lambda r: r.process_id)]


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------


def worker_init(spec_path: str) -> tuple[dict, int]:
    """Join the distributed job described by `spec_path` -> (spec, pid).

    Must run before anything touches jax devices: it routes through
    `repro.runtime.dist.initialize`, which forces the per-process
    emulated device count into XLA_FLAGS and switches CPU collectives to
    gloo before `jax.distributed.initialize`."""
    with open(spec_path) as f:
        spec = json.load(f)
    pid = int(os.environ["MHRUN_PROCESS_ID"])
    from repro.runtime import dist

    dist.initialize(
        spec["coordinator"],
        int(spec["num_processes"]),
        pid,
        local_device_count=int(spec["local_devices"]),
    )
    return spec, pid


def write_result(spec: dict, pid: int, payload: dict) -> None:
    """Report this worker's payload atomically (rename) so the runner
    never reads a half-written JSON."""
    path = os.path.join(spec["outdir"], f"result.{pid}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(payload, f, indent=1)
    os.replace(path + ".tmp", path)


def worker_main(spec_path: str, scenarios: dict[str, Any]) -> int:
    """Generic worker entrypoint: init, dispatch `spec['scenario']` from
    `scenarios` (a name -> fn(spec, pid) registry), report, exit code.
    Exceptions are reported as `{"error": repr}` with exit 1 so the
    runner can distinguish 'scenario failed' from 'process vanished'."""
    spec, pid = worker_init(spec_path)
    try:
        fn = scenarios[spec["scenario"]]
        payload = fn(spec, pid) or {}
    except BaseException as e:  # noqa: BLE001 - reported to the runner
        import traceback

        traceback.print_exc()
        write_result(spec, pid, {"error": f"{type(e).__name__}: {e}"})
        return 1
    write_result(spec, pid, payload)
    return 0


__all__ = [
    "HostResult",
    "free_port",
    "require_success",
    "run",
    "worker_init",
    "worker_main",
    "write_result",
]
