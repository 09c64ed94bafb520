"""lrc pools on ecutil's one-dispatch device path.

Encode, decode and decode_concat of lrc profiles whose layers are
jerasure reed_sol_van run as one GF(2^8) matmul each, lowered from the
plugin's own layered arithmetic; their bytes must equal the per-stripe
host path's, the plain reference's (benchmark/ref/lrc.py) and the
golden corpus's.  The tpu plugin's bytes and transfers stay as before,
a clay profile whose scalar code no byte probe can lower keeps the
per-stripe path, and a clay pool's dispatches are counted like lrc's.
"""
import itertools
import json
import os

import numpy as np
import pytest

from benchmark.ref import lrc as ref
from ceph_tpu.ec import registry, repairc
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ec.kernels import bitmatmul
from ceph_tpu.osd import ecutil

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
LAYERS = json.dumps([["_cDD_cDD", ""], ["cDDD____", ""],
                     ["____cDDD", ""]])
PROFILES = {
    "k4m2l3": {"k": "4", "m": "2", "l": "3"},
    "k6m3l3": {"k": "6", "m": "3", "l": "3"},
    "mapping": {"mapping": "__DD__DD", "layers": LAYERS},
}
CHUNK = 128
STRIPES = 3


def _code(name: str):
    return registry.factory("lrc", dict(PROFILES[name]))


def _host(name: str):
    """The same profile with no batched API: ecutil's per-stripe path."""
    ec = _code(name)
    ec.encode_batch = ec.decode_batch = None
    return ec


def _ref_layout(name: str) -> tuple[str, list[str]]:
    p = PROFILES[name]
    if "l" in p:
        return ref.kml(int(p["k"]), int(p["m"]), int(p["l"]))
    return p["mapping"], [ly for ly, _ in json.loads(p["layers"])]


def _payload(k: int, seed: int = 11) -> bytes:
    return np.random.default_rng(seed).bytes(STRIPES * k * CHUNK)


def _programs(ec) -> int:
    """Programs the plugin's per-signature cache has built."""
    return sum(repairc.cache_of(ec).stats()["compiles"].values())


@pytest.fixture
def kernel_calls(monkeypatch):
    """Device kernel (GFMatmul) calls: (bytes in, bytes out) of each."""
    calls = []
    orig = bitmatmul.GFMatmul.__call__

    def counted(self, data):
        out = orig(self, data)
        calls.append((data.nbytes, out.nbytes))
        return out
    monkeypatch.setattr(bitmatmul.GFMatmul, "__call__", counted)
    return calls


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_encode_matches_host_path_and_reference(name, kernel_calls):
    ec = _code(name)
    assert ecutil.batchable(ec) and not ecutil.batchable(_host(name))
    k = ec.get_data_chunk_count()
    sinfo = ecutil.StripeInfo(k, k * CHUNK)
    data = _payload(k)
    got = ecutil.encode(sinfo, ec, data)
    assert kernel_calls == [
        (len(data), len(data) // k * (ec.get_chunk_count() - k))]
    assert got == ecutil.encode(sinfo, _host(name), data)
    assert len(kernel_calls) == 1
    mapping, layers = _ref_layout(name)
    want = ref.shard_streams(data, mapping, layers, CHUNK)
    assert [got[i] for i in range(len(want))] == want


@pytest.mark.parametrize("name", ["k4m2l3", "mapping"])
def test_corpus_entries_on_the_device_path(name):
    """The golden corpus's one-stripe object, through ecutil's batched
    encode and a decode of every single erasure."""
    with open(os.path.join(FIXTURES, "ec_corpus.json")) as f:
        corpus = json.load(f)
    entry = next(e for e in corpus["entries"] if e["plugin"] == "lrc"
                 and e["profile"] == PROFILES[name])
    obj = bytes.fromhex(corpus["object_hex"])
    ec = _code(name)
    k, cs = ec.get_data_chunk_count(), entry["chunk_size"]
    assert k * cs == len(obj)
    sinfo = ecutil.StripeInfo(k, k * cs)
    got = ecutil.encode(sinfo, ec, obj)
    assert {str(i): c.hex() for i, c in got.items()} == entry["chunks"]
    mapping, layers = _ref_layout(name)
    assert ref.shard_streams(obj, mapping, layers, cs) == \
        [got[i] for i in range(len(got))]
    for lost in range(len(got)):
        avail = {i: c for i, c in got.items() if i != lost}
        assert ecutil.decode(sinfo, ec, avail, want=[lost])[lost] == \
            got[lost]
        assert ecutil.decode_concat(sinfo, ec, avail) == obj


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_every_signature_of_up_to_two_erasures(name, kernel_calls):
    """decode and decode_concat of every erasure set of one and two
    shards: one dispatch per call, the same bytes as the per-stripe
    path, and the same refusal where the layered walk cannot decode."""
    ec, host = _code(name), _host(name)
    n, k = ec.get_chunk_count(), ec.get_data_chunk_count()
    sinfo = ecutil.StripeInfo(k, k * CHUNK)
    data = _payload(k, seed=12)
    shards = ecutil.encode(sinfo, ec, data)
    data_shards = {ec.chunk_index(i) for i in range(k)}
    recoverable = 0
    for lost in itertools.chain(itertools.combinations(range(n), 1),
                                itertools.combinations(range(n), 2)):
        avail = {i: c for i, c in shards.items() if i not in lost}
        try:
            want = ecutil.decode(sinfo, host, avail, want=lost)
        except ErasureCodeError:
            with pytest.raises(ErasureCodeError):
                ecutil.decode(sinfo, ec, avail, want=lost)
            continue
        recoverable += 1
        assert want == {i: shards[i] for i in lost}
        del kernel_calls[:]
        assert ecutil.decode(sinfo, ec, avail, want=lost) == want
        assert len(kernel_calls) == 1, lost
        assert kernel_calls[0][1] == len(lost) * len(want[lost[0]])
        if not data_shards & set(lost):
            continue
        del kernel_calls[:]
        assert ecutil.decode_concat(sinfo, ec, avail) == data
        assert len(kernel_calls) == 1, lost
        assert ecutil.decode_concat(sinfo, host, avail) == data
    assert recoverable >= n


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_one_lost_data_chunk_decodes_from_its_local_group(name,
                                                         kernel_calls):
    """A lost data chunk is rebuilt from exactly the rest of its local
    layer, the first call building that signature's program and a
    second building nothing."""
    ec = _code(name)
    k = ec.get_data_chunk_count()
    sinfo = ecutil.StripeInfo(k, k * CHUNK)
    data = _payload(k, seed=13)
    shards = ecutil.encode(sinfo, ec, data)
    for i in range(k):
        lost = ec.chunk_index(i)
        group = ec.local_layer(lost).chunks_as_set - {lost}
        avail = {s: c for s, c in shards.items() if s != lost}
        inputs = sorted(ecutil.decode_extents(ec, [lost], avail))
        assert inputs == sorted(group) and ec.in_local_layer(inputs)
        for call in range(2):
            timings: dict = {}
            built = _programs(ec)
            del kernel_calls[:]
            assert ecutil.decode_concat(sinfo, ec, avail,
                                        timings=timings) == data
            assert kernel_calls == [(len(group) * STRIPES * CHUNK,
                                     STRIPES * CHUNK)]
            assert _programs(ec) - built == (call == 0)
            assert ("program" in timings) == (call == 0)
            assert {"stage", "h2d", "device", "d2h", "unstage"} <= \
                set(timings)
    compiles = ec._repairc_cache.stats()["compiles"]
    assert compiles["encode"] == 1
    assert sorted(compiles.values()) == [1] * (k + 1)


def test_a_profile_with_a_layer_no_byte_probe_can_lower_stays_on_host():
    ec = registry.factory("lrc", {
        "mapping": "DD_", "layers": json.dumps(
            [["DDc", "plugin=jerasure technique=reed_sol_van w=16"]])})
    assert ec.encode_batch is None and not ecutil.batchable(ec)


def test_clay_keeps_the_per_stripe_path(kernel_calls):
    """Over shec, a scalar code no byte probe can lower, a clay profile
    has no batched API: encode and decode loop over the stripes in the
    plugin's own interpreted code, one `kernel` region each.  (Over a
    byte matrix code clay takes the batched path:
    tests/test_ec_clay_device.py.)"""
    ec = registry.factory("clay", {"k": "4", "m": "2",
                                   "scalar_mds": "shec"})
    assert ec.encode_batch is None and not ecutil.batchable(ec)
    cs = ec.get_chunk_size(4 * 1024)
    sinfo = ecutil.StripeInfo(4, 4 * cs)
    data = np.random.default_rng(15).bytes(2 * sinfo.stripe_width)
    timings: dict = {}
    shards = ecutil.encode(sinfo, ec, data, timings=timings)
    assert list(timings) == ["kernel"]
    timings = {}
    assert ecutil.decode_concat(
        sinfo, ec, {i: c for i, c in shards.items() if i != 1},
        timings=timings) == data
    assert list(timings) == ["kernel"]
    assert kernel_calls == []


def test_tpu_bytes_and_transfers_unchanged(kernel_calls):
    """The tpu plugin: the bytes of its own per-stripe encode, one
    dispatch per call, the first k survivors staged, and no program
    region (its decode kernels live in its own table cache)."""
    ec = registry.factory("tpu", {"k": "4", "m": "2"})
    sinfo = ecutil.StripeInfo(4, 4 * CHUNK)
    data = _payload(4, seed=14)
    shards = ecutil.encode(sinfo, ec, data)
    assert kernel_calls == [(len(data), len(data) // 2)]
    for s in range(STRIPES):
        stripe = data[s * sinfo.stripe_width:(s + 1) * sinfo.stripe_width]
        enc = ec.encode(set(range(6)), stripe)
        for i in range(6):
            assert shards[i][s * CHUNK:(s + 1) * CHUNK] == \
                bytes(np.asarray(enc[i]))
    avail = {i: shards[i] for i in (1, 3, 4, 5)}
    timings: dict = {}
    del kernel_calls[:]
    assert ecutil.decode_concat(sinfo, ec, avail, timings=timings) == data
    n = STRIPES * CHUNK
    assert kernel_calls == [(4 * n, 2 * n)] and "program" not in timings
    assert ecutil.decode_extents(ec, [0, 2], avail) == \
        {s: [(0, 1)] for s in (1, 3, 4, 5)}


def test_tpu_with_a_chunk_mapping_on_the_device_path(kernel_calls):
    """A `mapping=` tpu profile takes the batched path too: its shards
    are the per-stripe path's and every single erasure decodes."""
    ec = registry.factory("tpu", {"k": "4", "m": "2",
                                  "mapping": "_DD_DD"})
    host = registry.factory("tpu", {"k": "4", "m": "2",
                                    "mapping": "_DD_DD"})
    host.encode_batch = None
    sinfo = ecutil.StripeInfo(4, 4 * CHUNK)
    data = _payload(4, seed=16)
    shards = ecutil.encode(sinfo, ec, data)
    assert shards == ecutil.encode(sinfo, host, data)
    for lost in range(6):
        avail = {i: c for i, c in shards.items() if i != lost}
        assert ecutil.decode(sinfo, ec, avail, want=[lost]) == \
            {lost: shards[lost]}
        assert ecutil.decode_concat(sinfo, ec, avail) == data
    assert len(kernel_calls) > 6


def test_degraded_read_of_an_lrc_pool_counts_one_local_decode():
    """MiniCluster: one OSD of an lrc k=4 m=2 l=3 pool down; each read
    of an object that lost a data shard is one device dispatch that
    stages the 3 chunks of its local group, counted as a local decode;
    the programs are built once per OSD and signature."""
    from ceph_tpu.testing import MiniCluster
    c = MiniCluster(n_osd=9, threaded=False)
    try:
        c.pump()
        c.wait_all_up()
        r = c.rados()
        r.mon_command({"prefix": "osd erasure-code-profile set",
                       "name": "lrc423",
                       "profile": {"plugin": "lrc", "k": "4", "m": "2",
                                   "l": "3",
                                   "crush-failure-domain": "host"}})
        r.pool_create("ecl", pg_num=4, pool_type="erasure",
                      erasure_code_profile="lrc423")
        c.pump()
        io = r.open_ioctx("ecl")
        rng = np.random.default_rng(29)
        size = 3 * 4 * 4096
        objs = {f"o{i}": rng.bytes(size) for i in range(8)}
        for oid, data in objs.items():
            io.write_full(oid, data)
        c.pump()

        def total(key):
            return sum(d.perf._c[key].value for d in c.osds.values())
        writes = total("ec_dispatches")
        assert writes == len(objs)
        assert total("ec_h2d_bytes") == len(objs) * size
        assert total("ec_d2h_bytes") == len(objs) * size
        built = total("ec_programs_built")
        assert 1 <= built <= 9
        omap = r.objecter.osdmap
        pool = r.pool_lookup("ecl")
        decoding = [oid for oid in objs if 0 in [
            omap.pg_to_up_acting_osds(omap.object_locator_to_pg(
                oid, pool))[2][p] for p in (0, 1, 4, 5)]]
        assert decoding
        c.kill_osd(0)
        r.mon_command({"prefix": "osd down", "ids": ["0"]})
        c.pump()
        for oid, data in objs.items():
            assert io.read(oid) == data, oid
        assert total("ec_dispatches") == writes + len(decoding)
        assert total("ec_local_decodes") == len(decoding)
        chunks = size // 4
        assert total("ec_h2d_bytes") == \
            len(objs) * size + len(decoding) * 3 * chunks
        assert total("ec_d2h_bytes") == \
            len(objs) * size + len(decoding) * chunks
        assert built < total("ec_programs_built") <= built + len(decoding)

        # a clay pool's write and read, the read after a decode made
        # outside ECBackend on this thread (as a peering rebuild makes
        # one): only the clay pool's dispatches are counted, the read's
        # from its d helpers' repair planes where it lost a data shard
        keys = ("ec_dispatches", "ec_h2d_bytes", "ec_d2h_bytes",
                "ec_local_decodes", "ec_programs_built",
                "ec_stripe_loop_calls")
        before = {key: total(key) for key in keys}
        r.mon_command({"prefix": "osd erasure-code-profile set",
                       "name": "clay42",
                       "profile": {"plugin": "clay", "k": "4", "m": "2",
                                   "crush-failure-domain": "host"}})
        r.pool_create("ecc", pg_num=4, pool_type="erasure",
                      erasure_code_profile="clay42")
        c.pump()
        cio = r.open_ioctx("ecc")
        for oid, data in objs.items():
            cio.write_full(oid, data)
        c.pump()
        lrc = _code("k4m2l3")
        sinfo = ecutil.StripeInfo(4, 4 * 4096)
        shards = ecutil.encode(sinfo, lrc, objs["o0"])
        del shards[0]
        assert ecutil.decode_concat(sinfo, lrc, shards) == objs["o0"]
        for oid, data in objs.items():
            assert cio.read(oid) == data, oid
        cmap, cpool = r.objecter.osdmap, r.pool_lookup("ecc")
        decoding = [oid for oid in objs if {0, 0x7FFFFFFF} & set(
            cmap.pg_to_up_acting_osds(cmap.object_locator_to_pg(
                oid, cpool))[2][:4])]
        assert decoding
        after = {key: total(key) - before[key] for key in keys}
        assert after.pop("ec_programs_built") >= 2
        reads = len(decoding)
        assert after == {
            "ec_dispatches": len(objs) + reads,
            "ec_h2d_bytes": len(objs) * size + reads * 5 * chunks // 2,
            "ec_d2h_bytes": len(objs) * size // 2 + reads * chunks,
            "ec_local_decodes": 0, "ec_stripe_loop_calls": 0}
    finally:
        c.shutdown()
