"""Mesh EC collectives: sharded encode/decode parity on the 8-device
virtual CPU mesh (ref: the per-shard fan-out it replaces,
src/osd/ECBackend.cc:2037-2070)."""
import numpy as np
import pytest

from ceph_tpu.dist import MeshECCoder, make_mesh
from ceph_tpu.ec import gf


@pytest.fixture(scope="module")
def devices():
    import jax
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs the 8-device CPU mesh (conftest env)")
    return devs


def oracle(coder, data_np):
    return np.stack([gf.gf_matmul_bytes(
        coder.encode_matrix[coder.k:], data_np[i])
        for i in range(data_np.shape[0])])


@pytest.mark.parametrize("shard_ways", [1, 2, 4])
def test_mesh_encode_parity(devices, shard_ways):
    k, m = 8, 4
    mesh = make_mesh(8, shard_ways=shard_ways, k=k)
    assert mesh.devices.shape == (8 // shard_ways, shard_ways)
    coder = MeshECCoder(k, m, mesh)
    rng = np.random.default_rng(shard_ways)
    S = 2 * mesh.devices.shape[0]
    data_np = rng.integers(0, 256, (S, k, 512), dtype=np.uint8)
    parity = np.asarray(coder.encode(coder.shard_data(data_np)))
    assert parity.shape == (S, m, 512)
    assert np.array_equal(parity, oracle(coder, data_np))


def test_mesh_decode_all_two_erasure_patterns(devices):
    k, m = 4, 2
    mesh = make_mesh(8, shard_ways=4, k=k)
    coder = MeshECCoder(k, m, mesh)
    rng = np.random.default_rng(9)
    S = 2 * mesh.devices.shape[0]
    data_np = rng.integers(0, 256, (S, k, 256), dtype=np.uint8)
    parity = np.asarray(coder.encode(coder.shard_data(data_np)))
    all_np = np.concatenate([data_np, parity], axis=1)
    import itertools
    for erasure in itertools.combinations(range(k + m), 2):
        decode_index = [i for i in range(k + m) if i not in erasure][:k]
        survivors = coder.shard_data(
            np.ascontiguousarray(all_np[:, decode_index, :]))
        rec = np.asarray(coder.decode(decode_index, list(erasure),
                                      survivors))
        for row, e in enumerate(erasure):
            assert np.array_equal(rec[:, row, :], all_np[:, e, :]), \
                erasure


def test_mesh_validation(devices):
    with pytest.raises(ValueError):
        make_mesh(8, shard_ways=3, k=8)   # 3 divides neither
    with pytest.raises(ValueError):
        make_mesh(10_000)
    mesh = make_mesh(8, shard_ways=2, k=8)
    with pytest.raises(ValueError):
        MeshECCoder(5, 2, mesh)           # k=5 not divisible by 2


def test_fabric_concurrent_stage_and_fetch(devices):
    """Device-contract regression: the fabric serializes mesh program
    launches.  k+m shard OSDs fetch their slices CONCURRENTLY while
    more writes stage — without the fabric's dispatch lock, two
    in-flight XLA programs could interleave their psum rendezvous
    across the shared devices and deadlock (observed live as the
    graft-entry dryrun's write op timing out)."""
    import threading

    from ceph_tpu.dist.fabric import ICIFabric
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry

    k, m, cs = 8, 4, 256
    ec = ErasureCodePluginRegistry.instance().factory(
        "tpu", {"k": str(k), "m": str(m)})
    fab = ICIFabric(8)
    assert fab.supports(ec)
    rng = np.random.default_rng(13)
    segs = {w: rng.integers(0, 256, 2 * k * cs, dtype=np.uint8)
            .tobytes() for w in range(3)}
    fab.stage_encode(("w", 0), ec, segs[0], cs)

    results: dict[tuple[int, int], bytes] = {}
    errors: list[BaseException] = []

    def fetch(write, shard):
        try:
            results[(write, shard)] = fab.fetch_chunk(("w", write),
                                                      shard)
        except BaseException as ex:   # noqa: BLE001 — surfaced below
            errors.append(ex)

    def stage(write):
        try:
            fab.stage_encode(("w", write), ec, segs[write], cs)
            for s in range(k + m):
                fetch(write, s)
        except BaseException as ex:   # noqa: BLE001
            errors.append(ex)

    threads = [threading.Thread(target=fetch, args=(0, s), daemon=True)
               for s in range(k + m)]
    threads += [threading.Thread(target=stage, args=(w,), daemon=True)
                for w in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads), \
        "fabric mesh dispatch deadlocked"
    assert not errors, errors

    # every fetched slice byte-identical to the host oracle
    for w, seg in segs.items():
        arr = np.frombuffer(seg, dtype=np.uint8).reshape(2, k, cs)
        parity = np.asarray(ec.encode_batch(arr))
        for s in range(k + m):
            want = (arr[:, s, :] if s < k
                    else parity[:, s - k, :]).tobytes()
            assert results[(w, s)] == want, (w, s)


def test_graft_entry_dryrun_inproc(devices):
    """The driver gate, run in-process on the virtual mesh."""
    import __graft_entry__ as g
    g.dryrun_multichip(8)


def test_graft_entry_never_drops_to_cpu_on_tpu(devices, monkeypatch):
    """On a TPU host with too few chips the dry run fails; it does not
    start a CPU child while this process holds the chip."""
    import jax

    import __graft_entry__ as g
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(g, "_dryrun_in_subprocess", lambda n: pytest.fail(
        "started a CPU child on a TPU host"))
    with pytest.raises(RuntimeError, match="TPU devices present"):
        g.dryrun_multichip(len(devices) + 1)
