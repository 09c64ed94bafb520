"""A plain model of an RBD image on an erasure-coded data pool.

RBD's default layout (stripe_unit = object size, stripe_count 1, from
librbd's documentation of `rbd create --object-size`): image byte x
lives in data object x // 2^order, at x % 2^order, named
`rbd_data.<image>.<objectno as 16 hex digits>`.  The image starts as a
seeded prefill and takes the acknowledged writes over it; each data
object's shards are gf256's reed_sol_van encode of its bytes.
"""
from __future__ import annotations

import numpy as np

from benchmark.ref import gf256


def data_name(image: str, objectno: int) -> str:
    return f"rbd_data.{image}.{objectno:016x}"


def locate(offset: int, order: int) -> tuple[int, int]:
    """(object number, offset in the object) of an image byte."""
    return offset >> order, offset & ((1 << order) - 1)


def prefill(seed: int, objectno: int, object_bytes: int) -> bytes:
    """The bytes the set-up writes to one data object."""
    return np.random.default_rng([seed, 3, objectno]).bytes(object_bytes)


class Image:
    """The image's bytes: the prefill, overlaid with writes."""

    def __init__(self, seed: int, size: int, order: int):
        self.seed, self.size, self.order = seed, size, order
        self._objects: dict[int, bytearray] = {}

    def object_bytes(self, objectno: int) -> bytes:
        got = self._objects.get(objectno)
        if got is None:
            return prefill(self.seed, objectno, 1 << self.order)
        return bytes(got)

    def write(self, offset: int, data: bytes) -> None:
        while data:
            objno, off = locate(offset, self.order)
            n = min(len(data), (1 << self.order) - off)
            obj = self._objects.get(objno)
            if obj is None:
                obj = self._objects[objno] = bytearray(
                    prefill(self.seed, objno, 1 << self.order))
            obj[off:off + n] = data[:n]
            offset, data = offset + n, data[n:]

    def read(self, offset: int, length: int) -> bytes:
        out = bytearray()
        while length:
            objno, off = locate(offset, self.order)
            n = min(length, (1 << self.order) - off)
            out += self.object_bytes(objno)[off:off + n]
            offset, length = offset + n, length - n
        return bytes(out)

    def shards(self, objectno: int, k: int, m: int,
               chunk: int) -> list[bytes]:
        """The k+m shard streams every live OSD should hold."""
        return gf256.shard_streams(self.object_bytes(objectno), k, m,
                                   chunk)
