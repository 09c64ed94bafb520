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
        # config-selected backend; pallas only lowers on TPU
        from ...common.options import global_config
        return (global_config()["ec_tpu_backend"] == "pallas"
                and jax.default_backend() == "tpu")

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


@functools.partial(jax.jit, static_argnames=("group",))
def gf_matmul_xla_grouped(bitmat_g: jax.Array, data: jax.Array,
                          group: int) -> jax.Array:
    """data (S, k, N) with S % group == 0; bitmat_g the grouped
    block-diagonal companion -> (S, r, N)."""
    s, k, n = data.shape
    d = data.reshape(s // group, group * k, n)
    bits = expand_bits(d)
    acc = jnp.matmul(bitmat_g, bits, preferred_element_type=jnp.int32)
    out = pack_bits(acc & 1)                    # (S/g, g*r, N)
    return out.reshape(s, -1, n)


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
    """One (stripe-group, N-tile) cell: plane-major unpack -> dense-tile
    MXU matmul -> &1 -> MXU pack-matmul; bit-planes only in VMEM."""
    data = data_ref[0].astype(jnp.int32)           # (gk, TN)
    planes = [((data >> c) & 1) for c in range(8)]
    bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8gk, TN)
    acc = jax.lax.dot_general(
        bitmat_ref[...], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)          # (8gr, TN)
    acc1 = (acc & 1).astype(jnp.int8)
    packed = jax.lax.dot_general(
        pack_ref[...], acc1, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)          # (gr, TN)
    out_ref[0] = packed.astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("group", "tile_n", "interpret"))
def gf_matmul_pallas_grouped(bitmat_gp: jax.Array, data: jax.Array,
                             group: int, tile_n: int,
                             interpret: bool = False) -> jax.Array:
    """Fused grouped kernel: grid (stripe-groups, N-tiles); the grid
    walks the stripe axis directly (no batch flatten/transpose).

    bitmat_gp: grouped_planar_bitmatrix; data (S, k, N) uint8 with
    S % group == 0 and N % tile_n == 0."""
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
        grid=(s // group, n // tile_n),
        in_specs=[
            pl.BlockSpec((gr8, gk8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gr, gr8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, gk, tile_n), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, gr, tile_n), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bitmat_gp, pmat, d)
    return out.reshape(s, -1, n)


PALLAS_MIN_TILE = 2048
PALLAS_TILE = 8192


# ---------------------------------------------------------------------------
# Full-width decode: device-resident survivor selection
# ---------------------------------------------------------------------------
# A degraded read holds the (S, n, N) chunk array in ARRIVAL layout —
# all n = k+m slots, erased slots carrying whatever garbage happens to
# sit there.  The staged formulation gathers k survivor rows into a
# dense (S, k, N) array on the HOST (np.stack + moveaxis), which a
# relay-era run (removed) put above the decode matmul itself; not
# measured on the current code.  The zero-column
# (nerrs x n) decode matrix (matrix_code.make_decode_matrix_full)
# makes the gather unnecessary: the selection IS the matrix.  But the
# naive full-width matmul unpacks 8n bit-planes instead of 8k — the
# round-3 measurement (PERF_NOTES) lost to staged decode (37 GB/s)
# exactly because the int32 unpack is the wall.
#
# The resolution here: the survivor selection derives STATICALLY from
# the matrix's nonzero columns (validated against the caller's
# validity mask), so
# * the Pallas kernel reads the full-width block but slices out only
#   the survivor rows in VMEM (static sublane slices, coalesced into
#   runs) before the bit-plane unpack — compute is IDENTICAL to the
#   staged path (8k planes, same grouped matmul), the gather costs a
#   VMEM copy, and no host staging exists at all;
# * the XLA fallback gathers survivor rows on DEVICE (one take) and
#   runs the same dense matmul — still no host stack/moveaxis.
# The extra n/k x HBM read of the full block is paid only by the
# Pallas path and is invisible while the kernel stays unpack/MXU-bound
# (PERF_NOTES round 2: far from the 819 GB/s HBM roof).

def _survivor_runs(idx: list[int]) -> list[tuple[int, int]]:
    """Sorted row indexes -> maximal contiguous [start, stop) runs, so
    the in-kernel gather is a handful of sublane slices, not k
    single-row copies."""
    runs: list[tuple[int, int]] = []
    for i in idx:
        if runs and runs[-1][1] == i:
            runs[-1] = (runs[-1][0], i + 1)
        else:
            runs.append((i, i + 1))
    return runs


def selection_from_matrix(mat_full: np.ndarray,
                          valid: np.ndarray | None = None) -> list[int]:
    """Survivor columns of a full-width decode matrix: the nonzero
    columns, checked against `valid` (length-n bool mask of slots
    whose content is real).  A nonzero column over an INVALID slot
    would fold garbage into the output — that is a caller bug, not a
    degraded mode, so it raises."""
    nz = [int(j) for j in np.flatnonzero(mat_full.any(axis=0))]
    if valid is not None:
        valid = np.asarray(valid, dtype=bool)
        bad = [j for j in nz if not valid[j]]
        if bad:
            raise ValueError(
                f"decode matrix has nonzero columns {bad} over slots "
                "the validity mask marks erased")
    return nz


def _gf_kernel_planar_select(runs, n, bitmat_ref, pack_ref, data_ref,
                             out_ref):
    """Full-width cell: static survivor slices out of the (g*n, TN)
    arrival block, then the identical plane-major unpack -> grouped
    matmul -> pack-matmul of the staged kernel.  `runs` are
    per-stripe-relative [start, stop) row runs; g stripes sit at
    offsets j*n."""
    full = data_ref[0]                              # (g*n, TN)
    g = full.shape[0] // n
    parts = [full[j * n + a:j * n + b, :]
             for j in range(g) for (a, b) in runs]
    data = (parts[0] if len(parts) == 1
            else jnp.concatenate(parts, axis=0)).astype(jnp.int32)
    planes = [((data >> c) & 1) for c in range(8)]
    bits = jnp.concatenate(planes, axis=0).astype(jnp.int8)  # (8gk, TN)
    acc = jax.lax.dot_general(
        bitmat_ref[...], bits, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)           # (8gr, TN)
    acc1 = (acc & 1).astype(jnp.int8)
    packed = jax.lax.dot_general(
        pack_ref[...], acc1, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)           # (gr, TN)
    out_ref[0] = packed.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=(
    "sel", "n", "group", "tile_n", "interpret"))
def gf_decode_pallas_grouped_full(bitmat_gp: jax.Array, data: jax.Array,
                                  sel: tuple, n: int, group: int,
                                  tile_n: int,
                                  interpret: bool = False) -> jax.Array:
    """Fused full-width decode: data (S, n, N) in arrival layout with
    S % group == 0, N % tile_n == 0; `sel` the static survivor column
    tuple; bitmat_gp the grouped planar companion of the DENSE
    (r x len(sel)) matrix."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    s, n_, nbytes = data.shape
    gr8, gk8 = bitmat_gp.shape
    gr = gr8 // 8
    d = data.reshape(s // group, group * n, nbytes)
    pmat = jnp.asarray(pack_matrix(gr))
    runs = tuple(_survivor_runs(list(sel)))
    kern = functools.partial(_gf_kernel_planar_select, runs, n)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((s // group, gr, nbytes),
                                       jnp.uint8),
        grid=(s // group, nbytes // tile_n),
        in_specs=[
            pl.BlockSpec((gr8, gk8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((gr, gr8), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, group * n, tile_n), lambda i, j: (i, 0, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, gr, tile_n), lambda i, j: (i, 0, j),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(bitmat_gp, pmat, d)
    return out.reshape(s, -1, nbytes)


@functools.partial(jax.jit, static_argnames=("sel",))
def gf_decode_xla_full(bitmat: jax.Array, data: jax.Array,
                       sel: tuple) -> jax.Array:
    """XLA full-width decode: DEVICE-resident survivor gather (one
    take along the chunk axis — no host stack/moveaxis) then the dense
    8k-contraction matmul."""
    survivors = jnp.take(data, jnp.asarray(sel, dtype=jnp.int32),
                         axis=-2)
    return gf_matmul_xla(bitmat, survivors)


class GFDecodeFull:
    """Cached device-resident decode for one full-width matrix.

    Holds the dense companion of mat_full restricted to its survivor
    columns (HBM-resident across calls, the ISA-L table-cache
    analogue) plus the static selection; __call__ consumes (..., n, N)
    arrival-layout chunk arrays with NO host-side staging."""

    def __init__(self, mat_full: np.ndarray,
                 valid: np.ndarray | None = None,
                 use_pallas: bool | None = None):
        self.mat_full = np.ascontiguousarray(mat_full, dtype=np.uint8)
        self.r, self.n = self.mat_full.shape
        self.sel = tuple(selection_from_matrix(self.mat_full, valid))
        if not self.sel:
            raise ValueError("decode matrix has no nonzero columns")
        self.mat = np.ascontiguousarray(self.mat_full[:, list(self.sel)])
        self.bitmat = jnp.asarray(
            companion_bitmatrix(self.mat.tobytes(), self.r,
                                len(self.sel)))
        #: group -> device-resident grouped planar companion; built on
        #: first use so repeat calls (the cached-signature hot path)
        #: never re-upload the weight matrix
        self._bgp: dict[int, jax.Array] = {}
        if use_pallas is None:
            from ...common.options import global_config
            use_pallas = (global_config()["ec_tpu_backend"] == "pallas"
                          and jax.default_backend() == "tpu")
        self.use_pallas = use_pallas

    def __call__(self, data, interpret: bool = False) -> jax.Array:
        data = jnp.asarray(data, dtype=jnp.uint8)
        *lead, n, nbytes = data.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} chunk slots, got {n}")
        s = int(np.prod(lead)) if lead else 1
        d = data.reshape(s, n, nbytes)
        if not self.use_pallas and not interpret:
            out = gf_decode_xla_full(self.bitmat, d, self.sel)
            return out.reshape(*lead, self.r, nbytes) if lead else out[0]
        group = 4 if s % 4 == 0 else 2 if s % 2 == 0 else 1
        tile = PALLAS_TILE if nbytes % PALLAS_TILE == 0 else (
            PALLAS_MIN_TILE if nbytes % PALLAS_MIN_TILE == 0 else 0)
        body_n = nbytes if tile else \
            (nbytes // PALLAS_MIN_TILE) * PALLAS_MIN_TILE
        if body_n == 0:
            out = gf_decode_xla_full(self.bitmat, d, self.sel)
            return out.reshape(*lead, self.r, nbytes) if lead else out[0]
        bgp = self._bgp.get(group)
        if bgp is None:
            bgp = self._bgp[group] = jnp.asarray(grouped_planar_bitmatrix(
                self.mat.tobytes(), self.r, len(self.sel), group))
        if tile:
            out = gf_decode_pallas_grouped_full(
                bgp, d, sel=self.sel, n=n, group=group, tile_n=tile,
                interpret=interpret)
        else:
            body = gf_decode_pallas_grouped_full(
                bgp, d[:, :, :body_n], sel=self.sel, n=n, group=group,
                tile_n=PALLAS_MIN_TILE, interpret=interpret)
            tail = gf_decode_xla_full(self.bitmat, d[:, :, body_n:],
                                      self.sel)
            out = jnp.concatenate([body, tail], axis=2)
        return out.reshape(*lead, self.r, nbytes) if lead else out[0]


def gf_matmul_pallas(mat: np.ndarray, data: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Fused-kernel entry on the BYTE matrix `mat` (r, k): picks the
    stripe group (4/2/1 dividing the batch) and N tiling, sends ragged
    tails through the XLA path.  data (..., k, N) -> (..., r, N)."""
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    r, k = mat.shape
    *lead, k_, n = data.shape
    s = int(np.prod(lead)) if lead else 1
    d = data.reshape(s, k, n)
    group = 4 if s % 4 == 0 else 2 if s % 2 == 0 else 1
    tile = PALLAS_TILE if n % PALLAS_TILE == 0 else (
        PALLAS_MIN_TILE if n % PALLAS_MIN_TILE == 0 else 0)
    body_n = n if tile else (n // PALLAS_MIN_TILE) * PALLAS_MIN_TILE
    if body_n == 0:
        B = jnp.asarray(companion_bitmatrix(mat.tobytes(), r, k))
        return gf_matmul_xla(B, data)
    bgp = jnp.asarray(grouped_planar_bitmatrix(mat.tobytes(), r, k, group))
    if tile:
        out = gf_matmul_pallas_grouped(bgp, d, group=group, tile_n=tile,
                                       interpret=interpret)
    else:
        body = gf_matmul_pallas_grouped(
            bgp, d[:, :, :body_n], group=group, tile_n=PALLAS_MIN_TILE,
            interpret=interpret)
        B = jnp.asarray(companion_bitmatrix(mat.tobytes(), r, k))
        tail = gf_matmul_xla(B, d[:, :, body_n:])
        out = jnp.concatenate([body, tail], axis=2)
    return out.reshape(*lead, r, n) if lead else out[0]
