"""Planar Pallas kernel parity (interpret mode, runs on CPU): the fused
grouped kernel must match the numpy GF oracle bit-for-bit across group/
tile/ragged-shape selections (ref kernel design: PERF_NOTES.md;
behavior parity target: src/erasure-code/isa ec_encode_data)."""
import numpy as np
import pytest

from ceph_tpu.ec import gf
from ceph_tpu.ec.kernels.bitmatmul import (companion_bitmatrix,
                                           gf_matmul_pallas,
                                           gf_matmul_xla,
                                           grouped_planar_bitmatrix,
                                           pack_matrix)


@pytest.mark.parametrize("s,k,m,n", [
    (8, 8, 4, 16384),   # g=4, tile 8192
    (7, 8, 4, 8192),    # odd batch -> g=1
    (2, 4, 2, 2048),    # g=2, min tile
    (1, 8, 4, 4096),    # single stripe
    (6, 3, 2, 2112),    # ragged tail (2048 body + 64 xla tail)
    (4, 8, 4, 1024),    # below min tile -> whole stripes per grid cell
    (4, 8, 4, 1000),    # below min tile, ragged lanes -> pure xla fallback
    (4, 2, 1, 6144),    # tiny code, multiple tiles
])
def test_pallas_parity_vs_oracle(s, k, m, n):
    rng = np.random.default_rng(k * 1000 + n)
    mat = gf.isa_rs_matrix(k, m)[k:]
    data = rng.integers(0, 256, (s, k, n), dtype=np.uint8)
    out = np.asarray(gf_matmul_pallas(mat, data, interpret=True))
    want = np.stack([gf.gf_matmul_bytes(mat, data[i]) for i in range(s)])
    assert np.array_equal(out, want)


def test_grouped_planar_matrix_structure():
    """The permuted block-diagonal matrix recomputes the interleaved
    one: B_planar[:, c*gk + j] == B_blockdiag[:, 8j + c]."""
    mat = np.ascontiguousarray(gf.isa_rs_matrix(8, 4)[8:])
    b1 = companion_bitmatrix(mat.tobytes(), 4, 8)
    bp = grouped_planar_bitmatrix(mat.tobytes(), 4, 8, 4)
    gk = 4 * 8
    assert bp.shape == (128, 256)
    # reconstruct the interleaved block-diag and compare per block
    for g in range(4):
        for j in range(8):
            for c in range(8):
                col_planar = c * gk + (g * 8 + j)
                np.testing.assert_array_equal(
                    bp[32 * g:32 * (g + 1), col_planar],
                    b1[:, 8 * j + c])


def test_pack_matrix_int8_wraparound():
    p = pack_matrix(4)
    assert p.shape == (4, 32)
    assert p[0, 7] == -128  # 1<<7 wraps; mod-256 exact after uint8 cast
    bits = np.ones((32, 4), dtype=np.int8)
    packed = (p.astype(np.int32) @ bits.astype(np.int32)).astype(np.uint8)
    assert (packed == 0xFF).all()


def test_pallas_matches_xla_path():
    """Both public paths agree (the plugin picks by backend/config)."""
    import jax.numpy as jnp
    mat = gf.isa_rs_matrix(6, 3)[6:]
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (4, 6, 4096), dtype=np.uint8)
    b = jnp.asarray(companion_bitmatrix(
        np.ascontiguousarray(mat).tobytes(), 3, 6))
    out_x = np.asarray(gf_matmul_xla(b, data))
    out_p = np.asarray(gf_matmul_pallas(mat, data, interpret=True))
    assert np.array_equal(out_x, out_p)
