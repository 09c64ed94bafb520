"""The `tpu` erasure-code plugin — the north-star component.

A JAX/Pallas GF(2^8) Reed-Solomon/Cauchy code behind the exact
ErasureCodeInterface boundary (ref: src/erasure-code/ErasureCodeInterface.h).
The GF matmul hot loop runs on the TPU MXU as a bit-plane GF(2) matmul
(see ceph_tpu.ec.kernels.bitmatmul); matrices, chunk sizes and padding follow
the isa/jerasure plugins so chunks are byte-identical to the CPU reference.

Techniques (profile `technique=`):
  reed_sol_van  - ISA-L gf_gen_rs_matrix (default; parity with isa plugin)
  cauchy        - ISA-L gf_gen_cauchy1_matrix
  jerasure_reed_sol_van, reed_sol_r6_op, cauchy_orig, cauchy_good
                - jerasure-compatible matrices (parity with jerasure plugin)

Beyond the interface, the plugin exposes a batched device-resident path
(`encode_batch`/`decode_batch`) used by the benchmark and the EC backend:
many stripes are encoded per dispatch so the host<->device boundary stays
off the hot path.
"""
from __future__ import annotations

import numpy as np

from .. import gf
from ..interface import ErasureCodeProfile, ErasureCodeError, to_int, \
    sanity_check_k_m
from ..matrix_code import DecodeTableCache, MatrixErasureCode, \
    make_decode_matrix, erasure_signature
from ..registry import ErasureCodePlugin

EC_TPU_DEFAULT_ALIGNMENT = 32  # match isa (EC_ISA_ADDRESS_ALIGNMENT)


def _matrices(technique: str, k: int, m: int) -> np.ndarray:
    eye = np.eye(k, dtype=np.uint8)
    if technique == "reed_sol_van":
        return gf.isa_rs_matrix(k, m)
    if technique == "cauchy":
        return gf.isa_cauchy_matrix(k, m)
    if technique == "jerasure_reed_sol_van":
        return np.vstack([eye, gf.jerasure_vandermonde_coding_matrix(k, m)])
    if technique == "reed_sol_r6_op":
        if m != 2:
            raise ErasureCodeError("reed_sol_r6_op requires m=2")
        return np.vstack([eye, gf.jerasure_r6_coding_matrix(k)])
    if technique == "cauchy_orig":
        return np.vstack([eye, gf.cauchy_original_coding_matrix(k, m)])
    if technique == "cauchy_good":
        return np.vstack([eye, gf.cauchy_good_coding_matrix(k, m)])
    raise ErasureCodeError(f"ENOENT: tpu technique={technique!r} not supported")


class ErasureCodeTpu(MatrixErasureCode):
    DEFAULT_K = "8"
    DEFAULT_M = "4"

    def __init__(self) -> None:
        super().__init__()
        self.technique = "reed_sol_van"
        self.alignment = EC_TPU_DEFAULT_ALIGNMENT
        self._encode_mm = None          # GFMatmul for coding rows
        #: signature -> GFMatmul, an LRU of ISA-L's 2516 entries so
        #: HBM-resident decode kernels can't grow unbounded across
        #: erasure patterns
        self._decode_mm = DecodeTableCache()

    def init(self, profile: ErasureCodeProfile) -> None:
        profile.setdefault("plugin", "tpu")
        self.technique = profile.setdefault("technique", "reed_sol_van")
        self.parse(profile)
        self.prepare()
        super().init(profile)

    def parse(self, profile: ErasureCodeProfile) -> None:
        super().parse(profile)
        self.k = to_int("k", profile, self.DEFAULT_K)
        self.m = to_int("m", profile, self.DEFAULT_M)
        self.alignment = to_int("tpu-alignment", profile,
                                str(EC_TPU_DEFAULT_ALIGNMENT))
        sanity_check_k_m(self.k, self.m)

    def get_chunk_size(self, object_size: int) -> int:
        # identical to the isa plugin (ErasureCodeIsa.cc:66-79) by default
        chunk_size = (object_size + self.k - 1) // self.k
        modulo = chunk_size % self.alignment
        if modulo:
            chunk_size += self.alignment - modulo
        return chunk_size

    def prepare(self) -> None:
        from ..kernels.bitmatmul import GFMatmul
        self._prepare(_matrices(self.technique, self.k, self.m))
        self._encode_mm = GFMatmul(self.encode_matrix[self.k:])

    # -- matmul backend on device -----------------------------------------
    def matmul(self, mat: np.ndarray, data: np.ndarray) -> np.ndarray:
        from ..kernels.bitmatmul import GFMatmul
        if self._encode_mm is not None and mat is not None and \
                mat.shape == self._encode_mm_shape and \
                np.array_equal(mat, self.encode_matrix[self.k:]):
            mm = self._encode_mm
        else:
            mm = GFMatmul(mat)
        return np.asarray(mm(data))

    @property
    def _encode_mm_shape(self):
        return (self.m, self.k)

    # -- batched device API (the perf path) -------------------------------
    def encode_batch(self, data):
        """(..., k, N) uint8 (host or device) -> (..., m, N) parity, on device.

        One dispatch encodes every stripe in the batch; keep inputs as jax
        arrays to avoid transfers between calls.
        """
        return self._encode_mm(data)

    def decode_batch(self, decode_index: list[int], erasures: list[int], data):
        """Reconstruct the shards `erasures` from survivor chunks.

        data: (..., k, N) chunks of the shards decode_index, in order.
        Returns (..., len(erasures), N) on device.  The decode companion
        matrix is cached per erasure signature (ISA-L table-cache analogue).
        """
        from ..kernels.bitmatmul import GFMatmul
        sig = erasure_signature(decode_index, erasures)
        mm = self._decode_mm.get(sig)
        if mm is None:
            # shard ids -> encode_matrix rows, through a `mapping=`
            rows = self.chunk_mapping or range(self.k + self.m)
            row = {s: r for r, s in enumerate(rows)}
            dmat = make_decode_matrix(self.encode_matrix, self.k,
                                      [row[i] for i in decode_index],
                                      [row[e] for e in erasures])
            mm = GFMatmul(dmat)
            self._decode_mm.put(sig, mm)
        return mm(data)


PLUGIN = ErasureCodePlugin("tpu", ErasureCodeTpu)
