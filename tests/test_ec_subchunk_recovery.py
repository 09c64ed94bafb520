"""Network-optimal (sub-chunk) single-shard EC recovery.

A regenerating code (clay) rebuilds one lost chunk from the repair
sub-chunk planes of d helpers instead of k whole chunks
(ref: ErasureCodeClay.cc:364 get_repair_subchunks; "Fast
Product-Matrix Regenerating Codes", arxiv 1412.3022).  These tests pin
the cluster path: ECSubRead v2 extent reads, ECPGShard serving
concatenated repair planes, ECBackend/ec_peering planning, the
recovery_bytes_read / recovery_bytes_rebuilt counters that prove the
saving, and byte-identical rebuilt shards.
"""
import sys

import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/", 1)[0])

from ceph_tpu.common.perf_counters import PerfCounters
from ceph_tpu.msg.messages import ECSubRead
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.ec_backend import ECBackend, pg_cid
from ceph_tpu.store import ObjectId

from test_ec_backend import Cluster, _payload

PGID = "1.0"


def _perf():
    p = PerfCounters("t")
    for key in ECBackend.PERF_KEYS:
        p.add_u64_counter(key)
    return p


def _counter(p, key):
    return p._c[key].value


@pytest.fixture
def clay_cl():
    cl = Cluster(k=4, m=2, plugin="clay")
    cl.backend.perf = _perf()
    return cl


def test_repair_plan_matches_plugin_math(clay_cl):
    """repair_chunk_extents covers exactly sub_chunk_no/q of a chunk."""
    ec = clay_cl.ec
    cs = clay_cl.backend.sinfo.chunk_size
    ext = ecutil.repair_chunk_extents(ec, 1, cs)
    assert sum(ln for _, ln in ext) == cs // ec.q
    # extents are in-bounds, non-overlapping, sorted
    last = 0
    for off, ln in ext:
        assert off >= last and off + ln <= cs
        last = off + ln


def test_handle_sub_read_serves_subchunk_extents(clay_cl):
    cl = clay_cl
    data = _payload(2 * cl.backend.sinfo.stripe_width, 3)
    assert cl.write("obj", 0, data)
    cs = cl.backend.sinfo.chunk_size
    ext = ecutil.repair_chunk_extents(cl.ec, 1, cs)
    msg = ECSubRead(pgid=PGID, tid=1, shard=2, to_read=[],
                    attrs_to_read=["obj"],
                    subchunks={"obj": list(ext)}, chunk_size=cs)
    reply = cl.shards[2].handle_sub_read(msg)
    assert not reply.errors
    stream = cl.stores[2].read(pg_cid(PGID), ObjectId("obj", shard=2),
                               0, 0)
    want = b"".join(stream[o:o + ln] for o, ln in
                    ecutil.expand_stream_extents(ext, cs, len(stream)))
    assert reply.buffers_read["obj"] == want
    assert len(want) < len(stream)
    # unknown oid -> per-oid error, not an exception
    bad = ECSubRead(pgid=PGID, tid=2, shard=2, to_read=[],
                    subchunks={"ghost": list(ext)}, chunk_size=cs)
    assert "ghost" in cl.shards[2].handle_sub_read(bad).errors


def test_subchunk_recovery_fewer_bytes_and_byte_identical(clay_cl):
    """The headline property: single-shard clay recovery ships
    strictly fewer bytes than k whole chunks (counter-verified at
    exactly d/q chunks) and the rebuilt shard is byte-identical."""
    cl = clay_cl
    b = cl.backend
    data = _payload(4 * b.sinfo.stripe_width, 7)
    assert cl.write("obj", 0, data)
    pre = cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1), 0, 0)
    cl.kill(1)
    cl.revive(1, wipe=True)
    assert cl.recover("obj", [1])
    post = cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1),
                             0, 0)
    assert post == pre
    read = _counter(b.perf, "recovery_bytes_read")
    rebuilt = _counter(b.perf, "recovery_bytes_rebuilt")
    assert rebuilt == len(pre)
    full_chunk_read = b.k * len(pre)
    assert 0 < read < full_chunk_read
    # clay reads d helpers x (1/q) of each chunk stream
    assert read == cl.ec.d * len(pre) // cl.ec.q
    # the object still reads back end to end
    assert cl.read("obj") == data
    # and the crc gate accepts the rebuilt shard (full-stream read
    # re-verifies the cumulative hash copied from the helpers)
    msg = ECSubRead(pgid=PGID, tid=9, shard=1,
                    to_read=[("obj", 0, 0)])
    assert not cl.shards[1].handle_sub_read(msg).errors


def test_subchunk_recovery_falls_back_on_helper_failure(clay_cl):
    """A helper EIO mid-repair degrades to the full-chunk rebuild —
    recovery still completes, just without the bandwidth saving."""
    cl = clay_cl
    b = cl.backend
    data = _payload(2 * b.sinfo.stripe_width, 11)
    assert cl.write("obj", 0, data)
    pre = cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1), 0, 0)
    cl.kill(1)
    cl.revive(1, wipe=True)
    # break one helper's chunk read (store-level EIO injection)
    cl.shards[2].inject_read_err("obj")
    assert cl.recover("obj", [1])
    cl.shards[2].clear_read_err("obj")
    post = cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1),
                             0, 0)
    assert post == pre


def test_non_regenerating_plugin_takes_full_path():
    """sub_chunk_count == 1 (tpu/isa-style codes): the planner refuses
    and the classic full-chunk rebuild runs (documented fallback)."""
    cl = Cluster(k=3, m=2, plugin="tpu")
    cl.backend.perf = _perf()
    assert not ecutil.supports_subchunk_repair(cl.ec)
    data = _payload(2 * cl.backend.sinfo.stripe_width, 5)
    assert cl.write("obj", 0, data)
    pre = cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1), 0, 0)
    cl.kill(1)
    cl.revive(1, wipe=True)
    assert cl.recover("obj", [1])
    assert cl.stores[1].read(pg_cid(PGID), ObjectId("obj", shard=1),
                             0, 0) == pre
    read = _counter(cl.backend.perf, "recovery_bytes_read")
    # full path: k whole chunk streams
    assert read == cl.backend.k * len(pre)


def test_multi_shard_loss_takes_full_path(clay_cl):
    """Sub-chunk repair is single-loss-only; two lost shards recover
    through the full decode + re-encode."""
    cl = clay_cl
    b = cl.backend
    data = _payload(2 * b.sinfo.stripe_width, 13)
    assert cl.write("obj", 0, data)
    pres = {s: cl.stores[s].read(pg_cid(PGID),
                                 ObjectId("obj", shard=s), 0, 0)
            for s in (1, 4)}
    for s in (1, 4):
        cl.kill(s)
        cl.revive(s, wipe=True)
    assert cl.recover("obj", [1, 4])
    for s in (1, 4):
        assert cl.stores[s].read(pg_cid(PGID),
                                 ObjectId("obj", shard=s), 0, 0) \
            == pres[s]


def test_ecsubread_v2_wire_roundtrip():
    """The subchunks/chunk_size fields ride the wire codec
    byte-faithfully (v2 evolution, schema-locked)."""
    from ceph_tpu.msg import encoding as wire
    msg = ECSubRead(pgid=(1, 0), tid=7, shard=2,
                    to_read=[("a", 0, 0)], attrs_to_read=["a"],
                    subchunks={"b": [(0, 512), (2048, 512)]},
                    chunk_size=4096)
    got = wire.decode(wire.encode(msg))
    assert got.subchunks == {"b": [[0, 512], [2048, 512]] } or \
        got.subchunks == {"b": [(0, 512), (2048, 512)]}
    assert got.chunk_size == 4096
    assert got.to_read in ([("a", 0, 0)], [["a", 0, 0]])


@pytest.fixture
def lrc_cl():
    cl = Cluster(plugin="lrc", profile={"k": "4", "m": "2", "l": "3"})
    cl.backend.perf = _perf()
    return cl


def test_lrc_recovery_reads_only_local_group(lrc_cl):
    """Fault-domain-aware LRC recovery: a single lost shard rebuilds
    from its LOCAL parity group — l helper chunks, counter-verified at
    l/k of the full-chunk baseline, byte-identical, and no read ever
    leaves the group (ISSUE 20 acceptance)."""
    cl = lrc_cl
    b = cl.backend
    data = _payload(4 * b.sinfo.stripe_width, 19)
    assert cl.write("obj", 0, data)
    ec = cl.ec
    # a shard in the second local group: all l helpers are remote, so
    # every helper read crosses the wire and the recorder sees it
    lost = 5
    group = ec.local_layer(lost).chunks_as_set
    pre = cl.stores[lost].read(pg_cid(PGID),
                               ObjectId("obj", shard=lost), 0, 0)
    cl.kill(lost)
    cl.revive(lost, wipe=True)
    reads = set()
    real_send = b.send

    def send(shard, msg):
        if isinstance(msg, ECSubRead):
            reads.add(shard)
        return real_send(shard, msg)
    b.send = send
    try:
        assert cl.recover("obj", [lost])
    finally:
        b.send = real_send
    post = cl.stores[lost].read(pg_cid(PGID),
                                ObjectId("obj", shard=lost), 0, 0)
    assert post == pre
    # in-group reads ONLY: the l survivors of the lost shard's local
    # parity group, never the k-survivor global decode set
    assert reads == group - {lost}
    read = _counter(b.perf, "recovery_bytes_read")
    rebuilt = _counter(b.perf, "recovery_bytes_rebuilt")
    assert rebuilt == len(pre)
    l = len(group) - 1
    assert read == l * len(pre)             # l whole helper chunks
    assert read < b.k * len(pre)            # strictly beats full path
    assert cl.read("obj") == data
    # crc gate: the rebuilt shard passes the full-stream hash check
    msg = ECSubRead(pgid=PGID, tid=9, shard=lost,
                    to_read=[("obj", 0, 0)])
    assert not cl.shards[lost].handle_sub_read(msg).errors


def test_lrc_local_parity_shard_recovers_in_group(lrc_cl):
    """Losing a LOCAL parity chunk (not data) also repairs within its
    group."""
    cl = lrc_cl
    b = cl.backend
    data = _payload(2 * b.sinfo.stripe_width, 23)
    assert cl.write("obj", 0, data)
    lost = 7                                # second group's parity
    group = cl.ec.local_layer(lost).chunks_as_set
    pre = cl.stores[lost].read(pg_cid(PGID),
                               ObjectId("obj", shard=lost), 0, 0)
    cl.kill(lost)
    cl.revive(lost, wipe=True)
    assert cl.recover("obj", [lost])
    assert cl.stores[lost].read(pg_cid(PGID),
                                ObjectId("obj", shard=lost),
                                0, 0) == pre
    read = _counter(b.perf, "recovery_bytes_read")
    assert read == (len(group) - 1) * len(pre)


def test_lrc_double_failure_takes_full_path(lrc_cl):
    """Two lost shards in the SAME local group exceed that group's
    repair capability: recovery degrades to the global decode and the
    data still comes back byte-identical."""
    cl = lrc_cl
    data = _payload(2 * cl.backend.sinfo.stripe_width, 29)
    assert cl.write("obj", 0, data)
    pres = {s: cl.stores[s].read(pg_cid(PGID),
                                 ObjectId("obj", shard=s), 0, 0)
            for s in (1, 2)}
    for s in (1, 2):
        cl.kill(s)
        cl.revive(s, wipe=True)
    assert cl.recover("obj", [1, 2])
    for s in (1, 2):
        assert cl.stores[s].read(pg_cid(PGID),
                                 ObjectId("obj", shard=s), 0, 0) \
            == pres[s]
    assert cl.read("obj") == data


def test_minicluster_clay_osd_out_recovers_with_subchunk_reads():
    """Cluster-level: remap a shard off an OSD in a clay pool; the
    peering rebuild uses repair-plane reads (counter-verified fewer
    bytes than k whole chunks) and data survives."""
    from ceph_tpu.testing import MiniCluster
    c = MiniCluster(n_osd=7, threaded=False)
    try:
        c.pump()
        c.wait_all_up()
        r = c.rados()
        r.mon_command({"prefix": "osd erasure-code-profile set",
                       "name": "clay42",
                       "profile": {"plugin": "clay", "k": "4", "m": "2",
                                   "crush-failure-domain": "host"}})
        r.pool_create("ecc", pg_num=4, pool_type="erasure",
                      erasure_code_profile="clay42")
        c.pump()
        io = r.open_ioctx("ecc")
        rng = np.random.default_rng(17)
        objs = {f"o{i}": rng.integers(0, 256, 4000 + i,
                                      dtype=np.uint8).tobytes()
                for i in range(4)}
        for oid, data in objs.items():
            io.write_full(oid, data)
        c.pump()
        r.mon_command({"prefix": "osd out", "ids": [0]})
        for _ in range(40):
            c.pump()
            if all(d.pgs_recovering() == 0 for d in c.osds.values()):
                break
        else:
            raise TimeoutError("clay recovery never finished")
        for oid, data in objs.items():
            assert io.read(oid) == data, oid
        read = sum(d.perf._c["recovery_bytes_read"].value
                   for d in c.osds.values())
        rebuilt = sum(d.perf._c["recovery_bytes_rebuilt"].value
                      for d in c.osds.values())
        assert rebuilt > 0
        # strictly fewer bytes than the k whole chunks the full-chunk
        # rebuild would have pulled for the same pushed shards
        assert read < 4 * rebuilt
    finally:
        c.shutdown()


def test_minicluster_lrc_osd_out_recovers_within_local_group():
    """Cluster-level lrc: remap a shard off an OSD; peering rebuilds
    each pushed shard from its LOCAL parity group (l=3 chunk reads,
    counter-verified at most (l+1)/k of the full-chunk baseline) and
    every object reads back intact (ISSUE 20 acceptance)."""
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
        rng = np.random.default_rng(23)
        objs = {f"o{i}": rng.integers(0, 256, 4000 + i,
                                      dtype=np.uint8).tobytes()
                for i in range(4)}
        for oid, data in objs.items():
            io.write_full(oid, data)
        c.pump()
        r.mon_command({"prefix": "osd out", "ids": [0]})
        for _ in range(40):
            c.pump()
            if all(d.pgs_recovering() == 0 for d in c.osds.values()):
                break
        else:
            raise TimeoutError("lrc recovery never finished")
        for oid, data in objs.items():
            assert io.read(oid) == data, oid
        read = sum(d.perf._c["recovery_bytes_read"].value
                   for d in c.osds.values())
        rebuilt = sum(d.perf._c["recovery_bytes_rebuilt"].value
                      for d in c.osds.values())
        assert rebuilt > 0
        # local-group repair: l=3 helper chunks per rebuilt shard,
        # i.e. at most (l+1)/k = 1x rebuilt-chunk volume -- and well
        # under the k=4 whole chunks of the classic path
        assert read <= 3 * rebuilt
        assert read < 4 * rebuilt
    finally:
        c.shutdown()
