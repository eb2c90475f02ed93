"""A run whose timed path is broken underneath reads `correct` false."""

import numpy as np
import pytest
from conftest import TINY, run_cell

from bench.system import Program, Stream


class Broken(Program):
    """The program, broken from the first request of the window on (the
    warm-up request runs clean)."""

    def __init__(self, spec):
        super().__init__(spec)
        self.requests = 0

    def compress(self, snapshot):
        self.requests += 1
        streams = super().compress(snapshot)
        return streams if self.requests == 1 else self.break_streams(snapshot, streams)

    def decompress(self, streams):
        out = super().decompress(streams)
        return out if self.requests == 1 else self.break_output(streams, out)

    def break_streams(self, snapshot, streams):
        return streams

    def break_output(self, streams, out):
        return out


class AlteredAnswer(Broken):
    """One value of one reconstruction moved by twice the bound."""

    def break_output(self, streams, out):
        name = sorted(out)[0]
        x = out[name]
        x.flat[x.size // 2] += 2 * self.policy.eb_rel * float(x.max() - x.min())
        return out


class HalfTheFields(Broken):
    """Half of the snapshot's fields are left out of the compression."""

    def break_streams(self, snapshot, streams):
        keep = sorted(snapshot)[: len(snapshot) // 2]
        return [s for s in streams if s.name in keep]


class AlteredStream(Broken):
    """A byte in the middle of each stream altered as it is produced."""

    def break_streams(self, snapshot, streams):
        out = []
        for s in streams:
            b = bytearray(s.data)
            b[len(b) // 2] ^= 0xFF
            out.append(Stream(s.name, s.codec, bytes(b), s.shape, s.dtype, s.bound))
        return out


class UnsolvedBound(Broken):
    """Stage I-II hands on a bound that is not a number."""

    def break_streams(self, snapshot, streams):
        return [Stream(s.name, s.codec, s.data, s.shape, s.dtype, float("nan"))
                for s in streams]


class UnwrittenOutput(Broken):
    """The decoder returns its output buffers as they were, never written."""

    def break_output(self, streams, out):
        return {s.name: np.zeros(s.shape, np.float32) for s in streams}


@pytest.mark.parametrize("fault", [AlteredAnswer, HalfTheFields, AlteredStream,
                                   UnsolvedBound, UnwrittenOutput])
def test_fault_reads_not_correct(tiny_root, capsys, fault):
    rc, res = run_cell(tiny_root, capsys, "--workload", TINY, "--seed", "9",
                       "--seconds", "0.3", "--trace", "0", make_system=fault)
    assert rc == 0
    assert res is not None and res["correct"] is False, res
