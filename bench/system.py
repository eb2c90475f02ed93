"""What a request drives: the program's entry points, or a reference in
their place.

A request is what an archive writer does with one snapshot: compress it,
keep only the streams (codec tag, bytes, shape, dtype), and read the
fields back from those streams alone. `Program` is the system under test;
`reference.QuantizeReference` stands in its place for the control.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np


@dataclass(frozen=True)
class Stream:
    """One field as the archive stores it, plus the bound Stage I-II solved."""

    name: str
    codec: str
    data: bytes
    shape: tuple[int, ...]
    dtype: str
    bound: float


class Program:
    """`repro.core.api.compress_pytree`, then `decompress_pytree` of a tree
    rebuilt from the streams' bytes alone (no `Selection` rides along)."""

    def __init__(self, policy_spec: dict):
        from repro.core import api
        from repro.core.policy import Policy

        self.api = api
        self.policy = Policy.from_spec(policy_spec)

    def compress(self, snapshot: dict) -> list[Stream]:
        ct = self.api.compress_pytree(snapshot, self.policy)
        return [
            Stream(name, cf.codec, cf.data, tuple(cf.shape), cf.dtype,
                   float(cf.selection.eb_abs) if cf.selection is not None else float("nan"))
            for name, cf in ct.fields.items()
        ]

    def decompress(self, streams: list[Stream]) -> dict:
        fields = {
            s.name: self.api.CompressedField(s.codec, s.data, s.shape, s.dtype)
            for s in streams
        }
        tree = self.api.CompressedTree(
            fields=fields, treedef=jax.tree_util.tree_structure(dict.fromkeys(fields, 0))
        )
        return self.api.decompress_pytree(tree)


def codec_split(streams: list[Stream]) -> dict[str, int]:
    out: dict[str, int] = {}
    for s in streams:
        out[s.codec] = out.get(s.codec, 0) + 1
    return dict(sorted(out.items()))


def stream_bytes(streams: list[Stream]) -> int:
    return sum(len(s.data) for s in streams)


def raw_bytes(snapshot: dict) -> int:
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize for x in snapshot.values())
