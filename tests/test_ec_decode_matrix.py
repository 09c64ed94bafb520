"""The dense (nerrs x k) decode matrix and the device decode built on
it (ref construction: ErasureCodeIsa.cc:252-306).

make_decode_matrix applied to the first k survivors rebuilds the
oracle chunks for EVERY erasure pattern (data, coding, and mixed
erasures up to m) at k=8,m=4 and k=4,m=2; the tpu plugin's
decode_batch and the fused Pallas kernel agree with it on sampled
patterns; a singular survivor submatrix and too few survivors are EIO;
and the HBM decode-kernel cache stays bounded.
"""
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import gf
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.matrix_code import DecodeTableCache, make_decode_matrix

CONFIGS = [(8, 4), (4, 2)]


def _all_patterns(k, m):
    n = k + m
    for r in range(1, m + 1):
        yield from itertools.combinations(range(n), r)


def _chunks(em, k, rng, nbytes=64):
    """(n, N) data and parity chunks of one random stripe."""
    data = rng.integers(0, 256, (k, nbytes), dtype=np.uint8)
    return np.concatenate([data, gf.gf_matmul_bytes(em[k:], data)])


@pytest.mark.parametrize("k,m", CONFIGS)
def test_decode_matrix_matches_oracle_all_patterns(k, m):
    """Exhaustive (numpy) sweep: for EVERY erasure pattern the dense
    ISA-ordered matrix applied to the first k survivors rebuilds the
    erased chunks exactly."""
    n = k + m
    em = gf.isa_rs_matrix(k, m)
    rng = np.random.default_rng(k * 100 + m)
    for erasures in _all_patterns(k, m):
        erasures = list(erasures)
        decode_index = [i for i in range(n) if i not in erasures][:k]
        dmat = make_decode_matrix(em, k, decode_index, erasures)
        assert dmat.shape == (len(erasures), k), erasures
        allc = _chunks(em, k, rng)
        got = gf.gf_matmul_bytes(dmat, allc[decode_index])
        np.testing.assert_array_equal(got, allc[erasures])


@pytest.mark.parametrize("k,m", CONFIGS)
def test_decode_batch_device_parity_sampled(k, m):
    """Device path (the plugin's decode_batch, XLA on the CPU) and the
    fused Pallas kernel in interpret mode against the oracle on
    representative patterns: data-only, coding-only, mixed, and
    max-erasure (each pattern is its own compiled kernel, so the
    exhaustive sweep stays numpy-side)."""
    from ceph_tpu.ec import registry
    from ceph_tpu.ec.kernels.bitmatmul import gf_matmul_pallas
    n = k + m
    tpu = registry.factory("tpu", {"k": str(k), "m": str(m)})
    em = np.asarray(tpu.encode_matrix)
    rng = np.random.default_rng(5)
    patterns = [[0], [k], [1, k + 1], list(range(m))]
    for erasures in patterns:
        erasures = sorted(set(erasures))[:m]
        decode_index = [i for i in range(n) if i not in erasures][:k]
        batch = np.stack([_chunks(em, k, rng, nbytes=2048)
                          for _ in range(2)])            # (S=2, n, N)
        want = batch[:, erasures, :]
        survivors = batch[:, decode_index, :]
        got = np.asarray(tpu.decode_batch(decode_index, erasures,
                                          survivors))
        np.testing.assert_array_equal(got, want)
        dmat = make_decode_matrix(em, k, decode_index, erasures)
        np.testing.assert_array_equal(
            np.asarray(gf_matmul_pallas(dmat, survivors, interpret=True)),
            want)


def test_singular_survivor_matrix_is_eio():
    """A singular survivor submatrix must surface as EIO (ref: the isa
    plugin's gf_invert_matrix failure -> -EIO)."""
    k = 2
    # deliberately degenerate: duplicate coding rows make the survivor
    # submatrix {2, 3} singular
    em = np.array([[1, 0],
                   [0, 1],
                   [1, 1],
                   [1, 1]], dtype=np.uint8)
    with pytest.raises(ErasureCodeError, match="EIO"):
        make_decode_matrix(em, k, [2, 3], [0, 1])


def test_decode_too_few_survivors_is_eio():
    """ecutil.decode with k-1 survivors refuses with EIO before it
    stages anything for the device."""
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil
    k, m = 4, 2
    tpu = registry.factory("tpu", {"k": str(k), "m": str(m)})
    cs = tpu.get_chunk_size(k * 64)
    sinfo = ecutil.StripeInfo(k, k * cs)
    have = {i: bytes(cs) for i in (1, 3, 5)}              # k-1 shards
    with pytest.raises(ErasureCodeError, match="EIO"):
        ecutil.decode(sinfo, tpu, have, want=[0])


def test_decode_table_cache_cost_weighted_eviction():
    """The LRU is a COST bound, not an entry count: each entry charges
    its cost, and the oldest entries evict when the budget is
    exceeded (repair programs weight by matrix bytes)."""
    c = DecodeTableCache(capacity=10)
    c.put("d1", "mat1", cost=4)
    c.put("d2", "mat2", cost=4)
    c.put("big-1", "bigmat1", cost=6)        # 14 > 10: evicts d1
    assert c.get("d1") is None
    assert c.get("d2") == "mat2"             # refreshed (MRU)
    assert c.get("big-1") == "bigmat1"
    assert c.total_cost() == 10
    # heavier entries cost more, so fitting a second one evicts BOTH
    # older entries (16 -> 12 -> 6): the bound is bytes, not count
    c.put("big-2", "bigmat2", cost=6)
    assert c.get("d2") is None
    assert c.get("big-1") is None
    assert c.get("big-2") == "bigmat2"
    assert c.total_cost() == 6
    # a single over-budget entry still caches (never thrash to empty)
    c.put("huge", "hugemat", cost=99)
    assert c.get("huge") == "hugemat"
    assert len(c) >= 1


def test_tpu_plugin_decode_cache_bounded_across_patterns():
    """Driving many distinct erasure signatures through the plugin
    must not grow the HBM kernel cache past its length: each
    signature charges one."""
    from ceph_tpu.ec import registry
    tpu = registry.factory("tpu", {"k": "4", "m": "2"})
    tpu._decode_mm.capacity = 6              # room for 6 signatures
    rng = np.random.default_rng(0)
    n = 6
    for erasures in itertools.combinations(range(n), 2):
        decode_index = [i for i in range(n) if i not in erasures][:4]
        survivors = rng.integers(0, 256, (1, 4, 64), dtype=np.uint8)
        tpu.decode_batch(decode_index, list(erasures), survivors)
    assert tpu._decode_mm.total_cost() == len(tpu._decode_mm) == 6
