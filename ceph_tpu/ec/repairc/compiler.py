"""Probe-based repair-program compilation + fused execution.

Every repair this tree performs — clay's pairwise-coupled plane walk,
lrc's local-group decode, a plain MDS decode-matrix apply — is
GF(2^8)-linear in the helper bytes: each rebuilt byte is a fixed
GF-linear combination of the gathered helper bytes, with coefficients
determined only by the erasure signature.  So the compiler does not
reimplement any plugin's math: it *extracts* the linear map by running
the plugin's own interpreted repair over basis probes at sub-chunk
size 1 (helper plane j := the byte 0x01, all others zero, yielding
column j of the repair matrix, since 0x01 is the field's
multiplicative identity), then lowers the whole schedule to

    gather survivor planes -> one grouped GF(2^8) matmul -> scatter

executed through the existing device kernels (GFMatmul: Pallas on the
TPU, the XLA bit-plane matmul elsewhere) or the numpy
oracle.  Probing costs `total_planes` interpreted 1-byte-sub-chunk
repairs per signature — paid once, then cached (see cache.py).
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from .. import gf
from ..interface import ErasureCodeError
from .plan import RepairPlan


def interpret_plan(ec, plan: RepairPlan,
                   helper_bufs: Mapping[int, np.ndarray],
                   chunk_size: int) -> dict[int, np.ndarray]:
    """One stripe of the plugin's own interpreted repair: helper
    buffers hold exactly the plan's gathered planes (full chunks when
    the extents cover the chunk, repair planes otherwise).  This is
    the reference semantics the compiled program must match
    byte-for-byte — probes and the parity tests both run through it."""
    chunks = {h: np.asarray(helper_bufs[h], dtype=np.uint8)
              for h, _ in plan.helpers}
    out = ec.decode(set(plan.lost), chunks, chunk_size)
    return {i: np.asarray(out[i], dtype=np.uint8) for i in plan.lost}


def compile_program(ec, plan: RepairPlan) -> "RepairProgram":
    """Derive the signature's repair matrix by basis probes through
    the interpreted path and wrap it as an executable program."""
    sub_no = plan.sub_chunk_no
    rows = plan.output_planes()
    cols = plan.total_planes()
    planes = {h: sum(c for _, c in ext) for h, ext in plan.helpers}

    def probe(shard=None, plane=0):
        bufs = {h: np.zeros(planes[h], dtype=np.uint8)
                for h, _ in plan.helpers}
        if shard is not None:
            bufs[shard][plane] = 1
        return interpret_plan(ec, plan, bufs, sub_no)

    # linearity guard: a plugin whose repair is affine (or stateful)
    # would silently mis-compile — all-zero input must rebuild zeros
    zero = probe()
    for i in plan.lost:
        if zero[i].any():
            raise ErasureCodeError(
                f"repairc: plan {plan.signature()} is not GF-linear "
                f"(zero probe rebuilt non-zero shard {i})")

    mat = np.zeros((rows, cols), dtype=np.uint8)
    col = 0
    for h, _ in plan.helpers:
        for p in range(planes[h]):
            out = probe(h, p)
            for i, lost in enumerate(plan.lost):
                mat[i * sub_no:(i + 1) * sub_no, col] = out[lost]
            col += 1
    return RepairProgram(plan, mat)


def compile_encode(ec) -> "RepairProgram":
    """Derive the code's generator, its coding shards as a GF-linear map
    of its data shards, by basis probes through the plugin's own
    encode_chunks at one byte per sub-chunk plane: at chunk size
    sub_chunk_count, data shard j's plane p := 0x01, the others zero,
    yields column j * sub_chunk_count + p.  Probing decode cannot stand
    in for it: a layered (lrc) decode walk need not rebuild every
    coding shard from the data shards alone.  The program's plan names
    the coding shards as `lost` and the data shards, whole, as helpers,
    both ascending, which is the order of the code's chunk mapping."""
    k = ec.get_data_chunk_count()
    n = ec.get_chunk_count()
    sub = ec.get_sub_chunk_count()
    plan = RepairPlan.make([ec.chunk_index(i) for i in range(k, n)],
                           {ec.chunk_index(i): [(0, sub)] for i in range(k)},
                           sub_chunk_no=sub)

    def probe(shard=None, plane=0) -> np.ndarray:
        encoded = {s: np.zeros(sub, dtype=np.uint8) for s in range(n)}
        if shard is not None:
            encoded[shard][plane] = 1
        ec.encode_chunks(set(range(n)), encoded)
        return np.concatenate([encoded[c] for c in plan.lost])

    if probe().any():
        raise ErasureCodeError("repairc: encode is not GF-linear "
                               "(zero data encoded non-zero parity)")
    mat = np.stack([probe(h, p) for h in plan.helper_ids()
                    for p in range(sub)], axis=1)
    return RepairProgram(plan, mat)


class RepairProgram:
    """A compiled erasure-signature repair: gather -> matmul -> scatter.

    The matrix is (output_planes x total_planes) over GF(2^8); `run`
    folds every stripe of the object into the columns of ONE matmul,
    so a whole-object rebuild is a single fused dispatch regardless of
    stripe count.  The device kernel object (GFMatmul — HBM-resident
    companion bit-matrix, jit-cached per data shape) is built lazily
    and rides in the program cache with its program.
    """

    def __init__(self, plan: RepairPlan, matrix: np.ndarray):
        self.plan = plan
        self.matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        self._kernel = None

    @property
    def kernel(self):
        """The device kernel (GFMatmul) over this program's matrix:
        (..., total_planes, N) -> (..., output_planes, N), N bytes of
        each plane."""
        if self._kernel is None:
            from ..kernels.bitmatmul import GFMatmul
            self._kernel = GFMatmul(self.matrix)
        return self._kernel

    def cost(self) -> int:
        """LRU weight: the matrix footprint in bytes (the bit-plane
        companion built on device is 64x this, same for every entry,
        so relative weights are preserved)."""
        return int(self.matrix.size)

    # ------------------------------------------------------------ layout
    def _gather(self, helper_bufs: Mapping[int, bytes], chunk_size: int
                ) -> tuple[np.ndarray, int, int]:
        """Helpers' concatenated per-stripe plane bytes -> one dense
        (total_planes x nstripes*ssz) array, plan order."""
        plan = self.plan
        if chunk_size % plan.sub_chunk_no:
            raise ValueError("chunk size not sub-chunk aligned")
        ssz = chunk_size // plan.sub_chunk_no
        nstripes = None
        cols = []
        for h, ext in plan.helpers:
            planes_h = sum(c for _, c in ext)
            buf = np.frombuffer(helper_bufs[h], dtype=np.uint8) \
                if isinstance(helper_bufs[h], (bytes, bytearray,
                                               memoryview)) \
                else np.asarray(helper_bufs[h], dtype=np.uint8)
            block = planes_h * ssz
            if block == 0 or buf.size % block:
                raise ValueError(
                    f"helper {h} buffer ({buf.size}B) not aligned to "
                    f"its {block}B repair block")
            ns = buf.size // block
            if nstripes is None:
                nstripes = ns
            elif ns != nstripes:
                raise ValueError("helper buffers disagree on stripes")
            cols.append(buf.reshape(ns, planes_h, ssz)
                        .transpose(1, 0, 2).reshape(planes_h, ns * ssz))
        return np.concatenate(cols, axis=0), nstripes, ssz

    def _scatter(self, out: np.ndarray, nstripes: int, ssz: int
                 ) -> dict[int, bytes]:
        sub_no = self.plan.sub_chunk_no
        streams = {}
        for i, lost in enumerate(self.plan.lost):
            rowsl = out[i * sub_no:(i + 1) * sub_no]
            streams[lost] = np.ascontiguousarray(
                rowsl.reshape(sub_no, nstripes, ssz)
                .transpose(1, 0, 2)).tobytes()
        return streams

    # --------------------------------------------------------- execution
    def run(self, helper_bufs: Mapping[int, bytes], chunk_size: int,
            backend: str | None = None) -> dict[int, bytes]:
        """Rebuild every lost shard's chunk stream from the helpers'
        gathered plane bytes.  backend: "device" (default — Pallas/XLA
        via GFMatmul) or "numpy" (the host oracle)."""
        x, nstripes, ssz = self._gather(helper_bufs, chunk_size)
        if backend == "numpy":
            out = gf.gf_matmul_bytes(self.matrix, x)
        else:
            from ...common import jaxguard
            # staging is explicit inside GFMatmul (jnp.asarray); the
            # guard bans any other host<->device crossing in the
            # dispatch.  The asarray readback is the one intended
            # D2H sync, outside the guarded region like ecutil.decode.
            with jaxguard.guard_transfers():
                out_dev = self.kernel(x)
            out = np.asarray(out_dev)
        return self._scatter(out, nstripes, ssz)
