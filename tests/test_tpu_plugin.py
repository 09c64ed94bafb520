"""TPU plugin parity: chunks must be byte-identical to the CPU plugins
(the corpus-style non-regression requirement, ref: SURVEY.md §4 tier 4 /
src/test/erasure-code/ceph_erasure_code_non_regression.cc)."""
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import registry


@pytest.mark.parametrize("k,m,technique,cpu_plugin,cpu_profile", [
    (8, 4, "reed_sol_van", "isa", {"technique": "reed_sol_van"}),
    (4, 2, "cauchy", "isa", {"technique": "cauchy"}),
    (6, 3, "jerasure_reed_sol_van", "jerasure", {"technique": "reed_sol_van"}),
    (5, 2, "reed_sol_r6_op", "jerasure", {"technique": "reed_sol_r6_op"}),
    (4, 3, "cauchy_good", "jerasure",
     {"technique": "cauchy_good", "packetsize": "32"}),
])
def test_parity_with_cpu_plugin(k, m, technique, cpu_plugin, cpu_profile):
    tpu = registry.factory("tpu", {"k": str(k), "m": str(m),
                                   "technique": technique})
    profile = dict(cpu_profile, k=str(k), m=str(m))
    cpu = registry.factory(cpu_plugin, profile)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()

    n = k + m
    # encode through each plugin's own padding; compare on the common
    # chunk layout (pad the object so both produce the same chunk size)
    size = max(cpu.get_chunk_size(len(data)), tpu.get_chunk_size(len(data))) * k
    data = data + b"\0" * (size - len(data))
    enc_cpu = cpu.encode(set(range(n)), data)
    enc_tpu = tpu.encode(set(range(n)), data)
    for i in range(n):
        assert np.array_equal(enc_cpu[i], enc_tpu[i]), f"chunk {i} differs"

    # decode parity across erasure patterns
    for erasures in itertools.combinations(range(n), min(m, 2)):
        avail = {i: enc_tpu[i] for i in range(n) if i not in erasures}
        dec = tpu.decode(set(range(n)), avail)
        for i in range(n):
            assert np.array_equal(dec[i], enc_cpu[i]), (erasures, i)


def test_batched_encode_decode():
    tpu = registry.factory("tpu", {"k": "8", "m": "4"})
    rng = np.random.default_rng(11)
    stripes, n = 4, 512
    data = rng.integers(0, 256, (stripes, 8, n), dtype=np.uint8)
    parity = np.asarray(tpu.encode_batch(data))
    assert parity.shape == (stripes, 4, n)
    # oracle
    from ceph_tpu.ec import gf
    for s in range(stripes):
        want = gf.gf_matmul_bytes(tpu.encode_matrix[8:], data[s])
        assert np.array_equal(parity[s], want)
    # erase chunks 1, 9; survivors = first 8 of the rest
    decode_index = [0, 2, 3, 4, 5, 6, 7, 8]
    full = np.concatenate([data, parity], axis=1)  # (S, 12, n)
    survivors = full[:, decode_index, :]
    rec = np.asarray(tpu.decode_batch(decode_index, [1, 9], survivors))
    assert np.array_equal(rec[:, 0], data[:, 1])
    assert np.array_equal(rec[:, 1], parity[:, 1])


def test_pallas_path_matches_xla():
    """Force the pallas path in interpreter-compatible mode on CPU."""
    import jax
    from ceph_tpu.ec import gf
    from ceph_tpu.ec.kernels import bitmatmul
    rng = np.random.default_rng(3)
    mat = rng.integers(0, 256, (4, 8)).astype(np.uint8)
    data = rng.integers(0, 256, (8, 4096)).astype(np.uint8)
    want = gf.gf_matmul_bytes(mat, data)
    bm = bitmatmul.companion_bitmatrix(mat.tobytes(), 4, 8)
    got_xla = np.asarray(bitmatmul.gf_matmul_xla(bm, data))
    assert np.array_equal(got_xla, want)
    # pallas on CPU backend runs in interpret-ish mode only on TPU; guard
    if jax.default_backend() == "tpu":
        got_pl = np.asarray(bitmatmul.gf_matmul_pallas(
            bitmatmul.GFMatmul(mat).bitmat, data))
        assert np.array_equal(got_pl, want)


def test_ragged_tail_sizes():
    from ceph_tpu.ec.kernels.bitmatmul import GFMatmul
    from ceph_tpu.ec import gf
    rng = np.random.default_rng(5)
    mat = rng.integers(0, 256, (3, 5)).astype(np.uint8)
    mm = GFMatmul(mat, use_pallas=False)
    for n in (32, 100, 2048, 2080, 5000):
        data = rng.integers(0, 256, (5, n)).astype(np.uint8)
        assert np.array_equal(np.asarray(mm(data)),
                              gf.gf_matmul_bytes(mat, data))


def test_ecutil_decode_from_shard_bytes():
    """The served decode: ecutil.decode from a dict of per-shard bytes
    (what a primary holds after its sub-reads) rebuilds the erased
    shards' chunk streams exactly."""
    from ceph_tpu.osd import ecutil
    tpu = registry.factory("tpu", {"k": "4", "m": "2"})
    rng = np.random.default_rng(42)
    stripes, cs = 6, 512
    sinfo = ecutil.StripeInfo(4, 4 * cs)
    data = rng.integers(0, 256, stripes * 4 * cs, dtype=np.uint8)
    from ceph_tpu.ec import gf
    stripe_chunks = data.reshape(stripes, 4, cs)
    chunks = np.stack([np.concatenate(
        [d, gf.gf_matmul_bytes(tpu.encode_matrix[4:], d)])
        for d in stripe_chunks])                           # (S, 6, cs)
    shards = {i: chunks[:, i, :].tobytes() for i in range(6)}
    for erasures in ([1], [0, 5], [2, 3]):
        have = {i: b for i, b in shards.items() if i not in erasures}
        got = ecutil.decode(sinfo, tpu, have, want=erasures)
        for e in erasures:
            assert got[e] == shards[e], (erasures, e)
