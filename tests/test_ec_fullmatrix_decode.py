"""Device-resident survivor selection: the full-matrix decode path.

The staging-free decode consumes all n = k+m chunk slots in ARRIVAL
layout against the zero-column (nerrs x n) decode matrix
(matrix_code.make_decode_matrix_full) — "the selection IS the matrix".
These tests pin it byte-identical to the ISA-ordered
make_decode_matrix path and the numpy oracle across EVERY erasure
pattern (data, coding, and mixed erasures up to m) for k=8,m=4 and
k=4,m=2, plus the singular-submatrix EIO behavior and the HBM decode-
kernel cache bound (ref construction: ErasureCodeIsa.cc:252-306; the
formulation this replaces is the host survivor gather).
"""
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import gf
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.matrix_code import (DecodeTableCache,
                                     make_decode_matrix,
                                     make_decode_matrix_full)

CONFIGS = [(8, 4), (4, 2)]


def _all_patterns(k, m):
    n = k + m
    for r in range(1, m + 1):
        yield from itertools.combinations(range(n), r)


def _arrival_layout(em, k, m, erasures, rng, nbytes=64):
    """(n, N) chunk array with parity rows and GARBAGE in erased
    slots — what a degraded read actually holds."""
    n = k + m
    data = rng.integers(0, 256, (k, nbytes), dtype=np.uint8)
    parity = gf.gf_matmul_bytes(em[k:], data)
    allc = np.concatenate([data, parity], axis=0)
    garbled = allc.copy()
    for e in erasures:
        garbled[e] = rng.integers(0, 256, nbytes, dtype=np.uint8)
    return allc, garbled


@pytest.mark.parametrize("k,m", CONFIGS)
def test_full_matrix_equals_isa_path_and_oracle_all_patterns(k, m):
    """Exhaustive (numpy) sweep: for EVERY erasure pattern the
    zero-column full matrix applied to the arrival layout (garbage in
    erased slots) reproduces exactly what the dense ISA-ordered matrix
    produces on gathered survivors — and both rebuild the oracle
    chunks."""
    n = k + m
    em = gf.isa_rs_matrix(k, m)
    rng = np.random.default_rng(k * 100 + m)
    for erasures in _all_patterns(k, m):
        erasures = list(erasures)
        decode_index = [i for i in range(n) if i not in erasures][:k]
        dmat = make_decode_matrix(em, k, decode_index, erasures)
        full = make_decode_matrix_full(em, k, n, decode_index, erasures)
        # structure: zero outside decode_index, dense rows inside
        mask = np.zeros(n, dtype=bool)
        mask[decode_index] = True
        assert not full[:, ~mask].any(), erasures
        np.testing.assert_array_equal(full[:, decode_index], dmat)
        allc, garbled = _arrival_layout(em, k, m, erasures, rng)
        got_full = gf.gf_matmul_bytes(full, garbled)
        got_dense = gf.gf_matmul_bytes(dmat, garbled[decode_index])
        np.testing.assert_array_equal(got_full, got_dense)
        np.testing.assert_array_equal(got_full, allc[erasures])


@pytest.mark.parametrize("k,m", CONFIGS)
def test_decode_batch_full_device_parity_sampled(k, m):
    """Device path (XLA gather + Pallas-interpret kernel) vs the
    staged decode_batch on representative patterns: data-only,
    coding-only, mixed, and max-erasure (each pattern is its own
    compiled kernel, so the exhaustive sweep stays numpy-side)."""
    from ceph_tpu.ec import registry
    from ceph_tpu.ec.kernels.bitmatmul import GFDecodeFull
    n = k + m
    tpu = registry.factory("tpu", {"k": str(k), "m": str(m)})
    rng = np.random.default_rng(5)
    patterns = [[0], [k], [1, k + 1], list(range(m))]
    for erasures in patterns:
        erasures = sorted(set(erasures))[:m]
        decode_index = [i for i in range(n) if i not in erasures][:k]
        em = np.asarray(tpu.encode_matrix)
        allc0, garbled0 = _arrival_layout(em, k, m, erasures, rng,
                                          nbytes=2048)
        allc1, garbled1 = _arrival_layout(em, k, m, erasures, rng,
                                          nbytes=2048)
        batch = np.stack([garbled0, garbled1])        # (S=2, n, N)
        want = np.stack([allc0[erasures], allc1[erasures]])
        got = np.asarray(tpu.decode_batch_full(erasures, batch))
        np.testing.assert_array_equal(got, want)
        # staged path agreement on the same survivors
        staged = np.asarray(tpu.decode_batch(
            decode_index, erasures, batch[:, decode_index, :]))
        np.testing.assert_array_equal(got, staged)
        # fused Pallas kernel (interpret mode) off the same matrix
        full = make_decode_matrix_full(em, k, n, decode_index,
                                       erasures)
        valid = np.ones(n, dtype=bool)
        valid[erasures] = False
        mm = GFDecodeFull(full, valid, use_pallas=True)
        np.testing.assert_array_equal(
            np.asarray(mm(batch, interpret=True)), want)


def test_full_matrix_rejects_nonzero_invalid_columns():
    """A nonzero column over a slot the validity mask marks erased
    would fold garbage into the rebuild — caller bug, hard error."""
    from ceph_tpu.ec.kernels.bitmatmul import selection_from_matrix
    mat = np.zeros((2, 6), dtype=np.uint8)
    mat[:, [0, 1, 2, 3]] = 1
    valid = np.array([1, 1, 1, 0, 1, 1], dtype=bool)  # col 3 erased
    with pytest.raises(ValueError, match="validity mask"):
        selection_from_matrix(mat, valid)
    # consistent mask passes and selects exactly the nonzero columns
    valid[3] = True
    assert selection_from_matrix(mat, valid) == [0, 1, 2, 3]


def test_singular_survivor_matrix_is_eio():
    """A singular survivor submatrix must surface as EIO through both
    the dense and the full-matrix construction (ref: the isa plugin's
    gf_invert_matrix failure -> -EIO)."""
    k, m = 2, 2
    # deliberately degenerate: duplicate coding rows make the survivor
    # submatrix {2, 3} singular
    em = np.array([[1, 0],
                   [0, 1],
                   [1, 1],
                   [1, 1]], dtype=np.uint8)
    with pytest.raises(ErasureCodeError, match="EIO"):
        make_decode_matrix(em, k, [2, 3], [0, 1])
    with pytest.raises(ErasureCodeError, match="EIO"):
        make_decode_matrix_full(em, k, 4, [2, 3], [0, 1])


def test_decode_batch_full_too_few_valid_is_eio():
    from ceph_tpu.ec import registry
    tpu = registry.factory("tpu", {"k": "4", "m": "2"})
    valid = np.array([1, 1, 1, 0, 0, 1], dtype=bool)   # 4 valid...
    data = np.zeros((1, 6, 64), dtype=np.uint8)
    with pytest.raises(ErasureCodeError, match="EIO"):
        # ...but one of them is also erased -> only 3 usable
        tpu.decode_batch_full([0], data, valid=valid)


def test_decode_table_cache_cost_weighted_eviction():
    """The decode-kernel LRU is a COST bound, not an entry count:
    full-width entries charge n, dense entries k, and the oldest
    entries evict when the budget is exceeded (the HBM-resident
    kernel cache cannot grow unbounded across erasure patterns)."""
    c = DecodeTableCache(capacity=10)
    c.put("d1", "densemat1", cost=4)
    c.put("d2", "densemat2", cost=4)
    c.put("full-1", "fullmat1", cost=6)      # 14 > 10: evicts d1
    assert c.get("d1") is None
    assert c.get("d2") == "densemat2"        # refreshed (MRU)
    assert c.get("full-1") == "fullmat1"
    assert c.total_cost() == 10
    # full-width entries cost more, so fitting a second one evicts
    # BOTH older entries (16 -> 12 -> 6): the bound is bytes, not count
    c.put("full-2", "fullmat2", cost=6)
    assert c.get("d2") is None
    assert c.get("full-1") is None
    assert c.get("full-2") == "fullmat2"
    assert c.total_cost() == 6
    # a single over-budget entry still caches (never thrash to empty)
    c.put("huge", "hugemat", cost=99)
    assert c.get("huge") == "hugemat"
    assert len(c) >= 1


def test_tpu_plugin_decode_cache_bounded_across_patterns():
    """Driving many distinct erasure signatures through the plugin
    must not grow the HBM kernel cache past its width budget."""
    from ceph_tpu.ec import registry
    tpu = registry.factory("tpu", {"k": "4", "m": "2"})
    tpu._decode_mm.capacity = 4 * 6          # room for ~6 dense entries
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, (1, 4, 64), dtype=np.uint8)
    n = 6
    for erasures in itertools.combinations(range(n), 2):
        decode_index = [i for i in range(n) if i not in erasures][:4]
        survivors = rng.integers(0, 256, (1, 4, 64), dtype=np.uint8)
        tpu.decode_batch(decode_index, list(erasures), survivors)
    assert tpu._decode_mm.total_cost() <= tpu._decode_mm.capacity
    assert len(tpu._decode_mm) <= 6
    del data


def test_decode_batches_full_pipeline_matches_single_dispatch():
    """The double-buffered H2D pipeline yields exactly what one-shot
    decode_batch_full produces, in order."""
    from ceph_tpu.ec import registry
    k, m = 4, 2
    tpu = registry.factory("tpu", {"k": str(k), "m": str(m)})
    em = np.asarray(tpu.encode_matrix)
    rng = np.random.default_rng(9)
    erasures = [1, 4]
    batches = []
    wants = []
    for _ in range(3):
        allc, garbled = _arrival_layout(em, k, m, erasures, rng,
                                        nbytes=256)
        batches.append(np.stack([garbled]))
        wants.append(np.stack([allc[erasures]]))
    outs = [np.asarray(o) for o in
            tpu.decode_batches_full(erasures, batches)]
    assert len(outs) == 3
    for got, want in zip(outs, wants):
        np.testing.assert_array_equal(got, want)
