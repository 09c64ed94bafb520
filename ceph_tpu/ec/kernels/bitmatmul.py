"""GF(2^8) byte matmul as a GF(2) bit-plane matmul on the TPU MXU.

The TPU-first formulation of the erasure-code hot loop (the GF(2^8)
matrix-vector products that ISA-L's `ec_encode_data` AVX2 assembly computes
per 32-byte lane, ref: src/erasure-code/isa/ErasureCodeIsa.cc:129):

GF(2^8) multiplication by a constant c is GF(2)-linear in the bits of the
operand, so an (r x k) byte matrix over GF(2^8) lifts to an (8r x 8k) 0/1
companion matrix B with B[8i+t, 8j+c] = bit t of (mat[i,j] * x^c).  A byte
block (k, N) unpacks to bit-planes (8k, N); then

    out_bits = (B @ bits) mod 2        # one int8 matmul on the MXU
    out[i,n] = sum_t out_bits[8i+t, n] << t

XOR-accumulation across k inputs becomes mod-2 integer accumulation inside
the matmul, which is exactly what the MXU is good at.  The contraction
length is 8k <= 256, so int32 (or even bf16) accumulation is exact.

Two paths:
* `gf_matmul_xla`: pure jnp — XLA fuses unpack/pack around a dot_general;
* `gf_matmul_pallas`: a fused Pallas kernel that keeps the 8x bit-plane
  expansion in VMEM only (never materialized in HBM), grid over N tiles.

Both produce bytes identical to the numpy oracle (ceph_tpu.ec.gf) and hence
to the reference plugins.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import gf


def expand_bits(data: jax.Array) -> jax.Array:
    """(..., k, N) uint8 -> (..., 8k, N) int8 bit-planes (bit c of byte j
    at row 8j+c)."""
    *lead, k, n = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(*lead, 8 * k, n).astype(jnp.int8)


def pack_bits(out_bits: jax.Array) -> jax.Array:
    """(..., 8r, N) {0,1} int32 -> (..., r, N) uint8."""
    *lead, r8, n = out_bits.shape
    r = r8 // 8
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8)).astype(jnp.int32)
    planes = out_bits.reshape(*lead, r, 8, n)
    return (planes * weights[None, :, None]).sum(axis=-2).astype(jnp.uint8)


@jax.jit
def gf_matmul_xla(bitmat: jax.Array, data: jax.Array) -> jax.Array:
    """(8r x 8k) companion bit-matrix times (..., k, N) bytes -> (..., r, N).

    Leading axes of `data` are batch (stripes)."""
    bits = expand_bits(data)
    acc = jnp.matmul(bitmat, bits, preferred_element_type=jnp.int32)
    return pack_bits(acc & 1)


@functools.lru_cache(maxsize=512)
def companion_bitmatrix(mat_bytes: bytes, r: int, k: int) -> np.ndarray:
    mat = np.frombuffer(mat_bytes, dtype=np.uint8).reshape(r, k)
    return gf.expand_to_bitmatrix(mat).astype(np.int8)


class GFMatmul:
    """Cached, device-resident GF matmul for a fixed byte matrix.

    The companion bit-matrix lives in HBM across calls (the analogue of the
    ISA-L encode-table cache, ref: ErasureCodeIsaTableCache.cc); jit caches
    the compiled kernel per data shape.
    """

    def __init__(self, mat: np.ndarray, use_pallas: bool | None = None):
        self.mat = np.ascontiguousarray(mat, dtype=np.uint8)
        self.r, self.k = self.mat.shape
        if use_pallas is not None:
            self.use_pallas = use_pallas

    # Both resolve on first use, not in __init__: building a code (a
    # mon validating an EC profile) must not start the device backend.
    @functools.cached_property
    def bitmat(self) -> jax.Array:
        return jnp.asarray(
            companion_bitmatrix(self.mat.tobytes(), self.r, self.k))

    @functools.cached_property
    def use_pallas(self) -> bool:
        # the fused kernel only lowers on the TPU
        return jax.default_backend() == "tpu"

    def __call__(self, data) -> jax.Array:
        """data: (..., k, N) uint8 (device or host) -> (..., r, N) uint8."""
        data = jnp.asarray(data, dtype=jnp.uint8)
        if self.use_pallas:
            return gf_matmul_pallas(self.mat, data)
        return gf_matmul_xla(self.bitmat, data)


# ---------------------------------------------------------------------------
# Grouped (block-diagonal) formulation: full MXU tiles
# ---------------------------------------------------------------------------
# A single (8m x 8k) companion matmul uses a sliver of the 128x128 MXU
# tile (k=8,m=4: 32 of 128 rows, 64 of 128 contraction lanes).  Stacking
# g stripes' bit-planes into one column vector and the weights into a
# block-diagonal (8mg x 8kg) matrix turns g tiny matmuls into one dense-
# tile matmul: for g=4, (128 x 256) @ (256 x N) — full rows, double-pass
# contraction.  The reshape (S, k, N) -> (S/g, gk, N) is free (no data
# movement); only the weight matrix grows (by g, with zeros the MXU
# processes at full rate).

@functools.lru_cache(maxsize=512)
def grouped_bitmatrix(mat_bytes: bytes, r: int, k: int,
                      group: int) -> np.ndarray:
    """Block-diagonal stack of `group` copies of the companion matrix:
    (8r*g, 8k*g) int8."""
    b = companion_bitmatrix(mat_bytes, r, k)
    g = group
    out = np.zeros((8 * r * g, 8 * k * g), dtype=np.int8)
    for i in range(g):
        out[8 * r * i:8 * r * (i + 1), 8 * k * i:8 * k * (i + 1)] = b
    return out


# ---------------------------------------------------------------------------
# Pallas fused kernel (plane-major, pack-by-matmul)
# ---------------------------------------------------------------------------
# Design notes (measured on v5e, see PERF_NOTES.md):
# * The bit-plane expansion must never touch HBM: fused in VMEM per grid
#   cell.
# * Plane-major bit layout — all bit-0 planes, then all bit-1 planes —
#   lowers to 8 flat shift/mask passes with no sublane interleave; the
#   companion matrix's columns are permuted to match (free, host side).
# * The byte re-pack is itself a (gr x 8gr) matmul against a weight
#   matrix with P[i, 8i+t] = 1<<t: elementwise epilogues over the
#   8x-inflated mod-2 accumulator dominated the kernel before this.
# * Mosaic constraints: MXU accumulator must be int32; int8/int16
#   shifts and uint8 iota don't lower (and the int8 compare-mask
#   variant lowers but runs slower than int32 shifts).

@functools.lru_cache(maxsize=512)
def _planar_perm(gk: int) -> np.ndarray:
    """Column permutation taking byte-major bit rows (bit c of byte j at
    8j+c) to plane-major (at c*gk+j)."""
    return np.array([8 * j + c for c in range(8) for j in range(gk)],
                    dtype=np.int64)


@functools.lru_cache(maxsize=512)
def grouped_planar_bitmatrix(mat_bytes: bytes, r: int, k: int,
                             group: int) -> np.ndarray:
    """Block-diagonal companion stack with plane-major columns:
    (8rg, 8kg) int8, ready for the fused kernel."""
    bg = grouped_bitmatrix(mat_bytes, r, k, group)
    return np.ascontiguousarray(bg[:, _planar_perm(group * k)])


@functools.lru_cache(maxsize=64)
def pack_matrix(rows: int) -> np.ndarray:
    """(rows, 8*rows) int8 with P[i, 8i+t] = 1<<t — packs mod-2 bit rows
    back into bytes as a matmul.  1<<7 wraps to -128 in int8; the int32
    accumulation truncated to uint8 is still exact mod 256."""
    p = np.zeros((rows, 8 * rows), dtype=np.int8)
    for i in range(rows):
        for t in range(8):
            p[i, 8 * i + t] = np.int8(np.uint8(1 << t).view(np.int8))
    return p


def _gf_kernel_planar(bitmat_ref, pack_ref, data_ref, out_ref):
    """One (stripe-group block, N-tile) cell, per stripe group of the
    block: plane-major unpack -> dense-tile MXU matmul -> &1 -> MXU
    pack-matmul; bit-planes only in VMEM."""
    for i in range(data_ref.shape[0]):
        data = data_ref[i].astype(jnp.int32)           # (gk, TN)
        planes = [((data >> c) & 1) for c in range(8)]
        bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8gk, TN)
        acc = jax.lax.dot_general(
            bitmat_ref[...], bits, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)          # (8gr, TN)
        acc1 = (acc & 1).astype(jnp.int8)
        packed = jax.lax.dot_general(
            pack_ref[...], acc1, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)          # (gr, TN)
        out_ref[i] = packed.astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("group", "tile_n", "interpret",
                                    "block"))
def gf_matmul_pallas_grouped(bitmat_gp: jax.Array, data: jax.Array,
                             group: int, tile_n: int,
                             interpret: bool = False,
                             block: int = 1) -> jax.Array:
    """Fused grouped kernel: grid (stripe-group blocks, N-tiles); the
    grid walks the stripe axis directly (no batch flatten/transpose),
    `block` stripe groups per cell, which the kernel takes in turn.

    bitmat_gp: grouped_planar_bitmatrix; data (S, k, N) uint8 with
    S % (group * block) == 0 and N % tile_n == 0."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, k, n = data.shape
    gr8, gk8 = bitmat_gp.shape
    gk, gr = gk8 // 8, gr8 // 8
    d = data.reshape(s // group, gk, n)
    pmat = jnp.asarray(pack_matrix(gr))
    out = pl.pallas_call(
        _gf_kernel_planar,
        out_shape=jax.ShapeDtypeStruct((s // group, gr, n), jnp.uint8),
        grid=(s // group // block, n // tile_n),
        in_specs=[
            pl.BlockSpec((gr8, gk8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gr, gr8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block, gk, tile_n), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block, gr, tile_n),
                               lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bitmat_gp, pmat, d)
    return out.reshape(s, -1, n)


PALLAS_MIN_TILE = 2048
PALLAS_TILE = 8192
#: the widest contraction (8 x input rows x stripe group) a stripe
#: group is grown to: two passes of the v5e's 128-deep MXU; past it a
#: group adds only the block-diagonal's zeros
PALLAS_MAX_CONTRACTION = 256
#: the lane granule: a stripe narrower than PALLAS_MIN_TILE takes the
#: kernel whole only at a multiple of it
LANE = 128


def pallas_layout(s: int, k: int, n: int) -> tuple[int, int, int]:
    """(group, tile, block) of the fused kernel for s stripes of k input
    rows by n lanes: `group` stripes share one block-diagonal matmul (4,
    2 or 1 dividing s, and no more than keep its contraction 8*k*group
    within PALLAS_MAX_CONTRACTION); N tiles of `tile` lanes; `block`
    stripe groups per grid cell, above 1 only where a stripe's lanes
    are narrower than PALLAS_MIN_TILE (a sub-chunk code's planes), so
    that a cell still takes up to PALLAS_TILE lanes.  tile 0: no lane
    prefix the kernel takes (the XLA path serves it whole); a tile
    that does not divide n leaves a ragged tail to the XLA path."""
    group = next(g for g in (4, 2, 1)
                 if s % g == 0 and (8 * k * g <= PALLAS_MAX_CONTRACTION
                                    or g == 1))
    if n % PALLAS_TILE == 0:
        return group, PALLAS_TILE, 1
    if n >= PALLAS_MIN_TILE:
        return group, PALLAS_MIN_TILE, 1
    if n % LANE:
        return group, 0, 1
    groups = s // group
    block = max(b for b in range(1, PALLAS_TILE // n + 1)
                if groups % b == 0)
    return group, n, block


def gf_matmul_pallas(mat: np.ndarray, data: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Fused-kernel entry on the BYTE matrix `mat` (r, k): the layout of
    pallas_layout; ragged tails go through the XLA path.
    data (..., k, N) -> (..., r, N)."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    r, k = mat.shape
    *lead, k_, n = data.shape
    s = int(np.prod(lead)) if lead else 1
    d = data.reshape(s, k, n)
    group, tile, block = pallas_layout(s, k, n)
    body_n = n - n % tile if tile else 0
    if body_n == 0:
        B = jnp.asarray(companion_bitmatrix(mat.tobytes(), r, k))
        return gf_matmul_xla(B, data)
    bgp = jnp.asarray(grouped_planar_bitmatrix(mat.tobytes(), r, k, group))
    if body_n == n:
        out = gf_matmul_pallas_grouped(bgp, d, group=group, tile_n=tile,
                                       interpret=interpret, block=block)
    else:
        body = gf_matmul_pallas_grouped(
            bgp, d[:, :, :body_n], group=group, tile_n=tile,
            interpret=interpret)
        B = jnp.asarray(companion_bitmatrix(mat.tobytes(), r, k))
        tail = gf_matmul_xla(B, d[:, :, body_n:])
        out = jnp.concatenate([body, tail], axis=2)
    return out.reshape(*lead, r, n) if lead else out[0]
