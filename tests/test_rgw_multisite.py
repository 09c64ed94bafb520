"""RGW multisite: realm/zonegroup/zone period model, sharded
datalog, async site-to-site replication + the keystone auth satellite
(ref: src/rgw/rgw_sync.cc, rgw_data_sync.cc, rgw_period.cc,
rgw_auth_keystone.cc; ISSUE 5)."""
import io as _io
import json
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from xml.etree import ElementTree as ET

import pytest

from ceph_tpu.rgw import RGWGateway
from ceph_tpu.rgw.auth import KeystoneEngine, KeystoneError
from ceph_tpu.rgw.datalog import DataLog, is_dl_key, shard_obj
from ceph_tpu.rgw.multisite import (MultisiteAdmin, MultisiteError,
                                    sync_status_obj)
from ceph_tpu.testing import MiniCluster
from ceph_tpu.tools import rados_cli

VERS_ON = (b"<VersioningConfiguration>"
           b"<Status>Enabled</Status></VersioningConfiguration>")


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osd=4, threaded=True)
    c.wait_all_up()
    yield c
    c.shutdown()


@pytest.fixture(scope="module")
def ms(cluster):
    """The long-lived two-zone site: m1 master, m2 secondary.  Tests
    use per-test bucket names so they share it."""
    return cluster.rgw_multisite(zones=("m1", "m2"))


def req(gw, method, path, data=None, headers=None):
    r = urllib.request.Request(f"http://127.0.0.1:{gw.port}{path}",
                               data=data, method=method,
                               headers=headers or {})
    with urllib.request.urlopen(r, timeout=30) as resp:
        return resp.status, dict(resp.headers), resp.read()


def _wait(cond, timeout=30.0, interval=0.05):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _get_bytes(gw, bucket, key, vid=""):
    path = f"/{bucket}/{key}"
    if vid:
        path += f"?versionId={vid}"
    try:
        return req(gw, "GET", path)[2]
    except urllib.error.HTTPError:
        return None


def _dl_entries(gw, bucket):
    """Every datalog entry of every shard, in (shard, seq) order."""
    dl = DataLog(gw.io)
    out = []
    for s in range(gw._nshards(bucket)):
        ents, _ = dl.list(bucket, s, 0, 10_000)
        out.extend(ents)
    return out


# ------------------------------------------------------- period model

def test_period_model_staging_commit_adopt(cluster):
    r = cluster.rados()
    r.pool_create("msadm", pg_num=8)
    adm = MultisiteAdmin(r.open_ioctx("msadm"))
    assert adm.period_get()["epoch"] == 0
    with pytest.raises(MultisiteError):
        adm.zonegroup_create("zg")      # realm first
    adm.realm_create("gold")
    adm.zonegroup_create("zg")
    with pytest.raises(MultisiteError):
        adm.zone_create("z1", "nope")
    adm.zone_create("z1", "zg", endpoint="http://a", master=True)
    adm.zone_create("z2", "zg", endpoint="http://b")
    # edits stage: the committed period is still empty
    assert adm.period_get()["epoch"] == 0
    assert adm.period_commit() == 1
    p = adm.period_get()
    assert p["realm"] == "gold"
    assert p["zonegroups"]["zg"]["zones"]["z1"]["master"]
    assert not p["zonegroups"]["zg"]["zones"]["z2"]["master"]
    # a no-op commit must not bump the epoch
    assert adm.period_commit() == 1
    # exactly one master: flipping z2 demotes z1
    adm.zone_modify("z2", "zg", master=True)
    assert adm.period_commit() == 2
    zones = adm.period_get()["zonegroups"]["zg"]["zones"]
    assert zones["z2"]["master"] and not zones["z1"]["master"]
    # adopt: newer period replaces, older is refused
    newer = dict(adm.period_get(), epoch=9)
    assert adm.period_adopt(newer)
    assert adm.period_get()["epoch"] == 9
    assert not adm.period_adopt(dict(newer, epoch=3))
    assert adm.period_get()["epoch"] == 9


def test_period_epoch_propagates_between_zones(ms):
    """A topology commit on the master radiates to the secondary via
    the sync agent's period probe (the `period pull` analogue)."""
    m1, m2 = ms
    adm = m1.multisite.admin
    zg = m1.multisite.my_zonegroup()[0]
    adm.zone_create("m3", zg, endpoint="")  # endpoint-less: no peer
    epoch = adm.period_commit()
    assert epoch > 1
    assert _wait(lambda: (m2.multisite.refresh(force=True) or
                          m2.multisite.epoch == epoch))
    assert "m3" in m2.multisite.period["zonegroups"][zg]["zones"]


# ----------------------------------------------------------- datalog

def test_datalog_rides_the_index_transaction(ms):
    m1, _ = ms
    req(m1, "PUT", "/dlb")
    for i in range(3):
        req(m1, "PUT", f"/dlb/k{i}", b"x%d" % i)
    ents = _dl_entries(m1, "dlb")
    puts = [e for e in ents if e["op"] == "put"]
    assert len(puts) == 3
    assert all(e["trace"] == ["m1"] for e in puts)
    # the record lives in the SAME omap object as the index entry it
    # describes (appended by cls in the same mutation batch — the
    # txn-atomicity contract)
    for e in puts:
        raw = m1.io.get_omap_vals(
            shard_obj("dlb", m1.shard_of("dlb", e["key"])))[0]
        assert e["key"] in raw
        assert any(is_dl_key(k) and json.loads(raw[k])["seq"] ==
                   e["seq"] for k in raw)
    # datalog keys never leak into listings or index dumps
    _, _, body = req(m1, "GET", "/dlb?list-type=2")
    keys = [el.text for el in ET.fromstring(body).iter("Key")]
    assert keys == ["k0", "k1", "k2"]
    assert not any(is_dl_key(k) for k in m1._index("dlb"))


def test_datalog_cursor_and_trim(ms):
    m1, _ = ms
    req(m1, "PUT", "/dlc")
    for i in range(6):
        req(m1, "PUT", "/dlc/same", b"v%d" % i)   # one shard
    s = m1.shard_of("dlc", "same")
    dl = DataLog(m1.io)
    ents, head = dl.list("dlc", s, 0, 100)
    assert head == 6 and [e["seq"] for e in ents] == list(range(1, 7))
    # cursor read: only entries past the marker
    ents, head = dl.list("dlc", s, 4, 100)
    assert [e["seq"] for e in ents] == [5, 6]
    # batch cap
    ents, _ = dl.list("dlc", s, 0, 2)
    assert [e["seq"] for e in ents] == [1, 2]
    # missing shard object reads as empty
    assert dl.list("nope", 0) == ([], 0)
    # trim drops entries but the head never regresses
    assert dl.trim("dlc", s, 4) == 4
    ents, head = dl.list("dlc", s, 0, 100)
    assert head == 6 and [e["seq"] for e in ents] == [5, 6]


# ------------------------------------------------- replication (E2E)

def test_e2e_convergence_plain_and_versioned(ms):
    """The acceptance E2E: plain writes, a versioned overwrite and
    deletes on the master converge byte-identical on the secondary."""
    m1, m2 = ms
    req(m1, "PUT", "/convp")
    req(m1, "PUT", "/convp/a", b"A-bytes")
    req(m1, "PUT", "/convp/b", b"B-bytes")
    req(m1, "DELETE", "/convp/b")
    req(m1, "PUT", "/convv")
    req(m1, "PUT", "/convv?versioning", VERS_ON)
    _, h1, _ = req(m1, "PUT", "/convv/v", b"V-one")
    _, h2, _ = req(m1, "PUT", "/convv/v", b"V-two")   # overwrite
    vid1, vid2 = h1["x-amz-version-id"], h2["x-amz-version-id"]
    _, hd, _ = req(m1, "DELETE", "/convv/v")          # delete marker
    dm_vid = hd["x-amz-version-id"]

    assert _wait(lambda: _get_bytes(m2, "convp", "a") == b"A-bytes")
    assert _wait(lambda: _get_bytes(m2, "convp", "b") is None)
    assert _wait(lambda: _get_bytes(m2, "convv", "v", vid2) == b"V-two")
    assert _get_bytes(m2, "convv", "v", vid1) == b"V-one"
    assert _get_bytes(m2, "convv", "v") is None       # dm is current
    # version stacks converge identically (vids, order, the marker)
    assert _wait(lambda: m2._index_entry("convv", "v") is not None)

    def stack(gw):
        return [(v["vid"], bool(v.get("dm")), v["mtime"], v["etag"])
                for v in gw._index_entry("convv", "v")["versions"]]
    assert _wait(lambda: stack(m2) == stack(m1))
    assert [v[0] for v in stack(m2)] == [dm_vid, vid2, vid1]
    # both agents report caught up, 0 behind shards
    assert _wait(lambda: m2.sync.caught_up() and m1.sync.caught_up())
    st = m2.sync.status()["sources"][0]
    assert st["behind_shards"] == 0 and st["lag_entries"] == 0
    # ... through the REST surface a remote `sync status` reads
    _, _, body = req(m2, "GET", "/admin/sync-status")
    rest = json.loads(body)
    assert rest["sources"][0]["caught_up"]


def test_inflight_multipart_does_not_wedge_sync(ms):
    """Multipart bookkeeping (.upload.<id>) shares the index omap but
    is not object state: the /admin/bucket dump a peer full-syncs
    from must carry objects only — the upload meta has no
    size/etag/mtime and used to crash the op synthesizer, aborting
    the whole peer round every tick (regression)."""
    m1, m2 = ms
    req(m1, "PUT", "/mpb")
    _, _, body = req(m1, "POST", "/mpb/big.bin?uploads")
    uid = ET.fromstring(body).find("UploadId").text
    req(m1, "PUT", f"/mpb/big.bin?partNumber=1&uploadId={uid}",
        b"P" * 1024)                    # upload stays in flight
    req(m1, "PUT", "/mpb/done", b"done-bytes")
    _, _, dump = req(m1, "GET", "/admin/bucket?name=mpb")
    keys = set(json.loads(dump))
    assert "done" in keys
    assert not [k for k in keys if k.startswith(".upload.")]
    # replication proceeds past the in-flight upload: converged,
    # caught up, nothing quarantined
    assert _wait(lambda: _get_bytes(m2, "mpb", "done") == b"done-bytes")
    assert _wait(lambda: m2.sync.caught_up())
    assert not [e for e in m2.sync.error_list()
                if e["bucket"] == "mpb"]


def test_delete_marker_removal_replicates(ms):
    """rmver of the delete marker restores the key on both zones."""
    m1, m2 = ms
    req(m1, "PUT", "/dmr")
    req(m1, "PUT", "/dmr?versioning", VERS_ON)
    req(m1, "PUT", "/dmr/k", b"alive")
    _, hd, _ = req(m1, "DELETE", "/dmr/k")
    dm_vid = hd["x-amz-version-id"]
    assert _wait(lambda: _get_bytes(m2, "dmr", "k") is None and
                 m2._index_entry("dmr", "k") is not None)
    req(m1, "DELETE", f"/dmr/k?versionId={dm_vid}")
    assert _get_bytes(m1, "dmr", "k") == b"alive"
    assert _wait(lambda: _get_bytes(m2, "dmr", "k") == b"alive")
    vids = [v["vid"] for v in m2._index_entry("dmr", "k")["versions"]]
    assert dm_vid not in vids


def test_overwrite_race_converges_deterministically(ms):
    """Conflicting same-key writes on both zones settle to ONE winner
    on both — newest (mtime, etag) wins, ties broken by etag so the
    zones cannot disagree."""
    m1, m2 = ms
    req(m1, "PUT", "/race")
    assert _wait(lambda: "race" in m2._buckets())
    req(m1, "PUT", "/race/k", b"AAAA")
    req(m2, "PUT", "/race/k", b"BBBB")

    def settled():
        if not (m1.sync.caught_up() and m2.sync.caught_up()):
            return False
        e1 = m1._index_entry("race", "k")
        e2 = m2._index_entry("race", "k")
        return (e1 and e2 and
                (e1["mtime"], e1["etag"]) == (e2["mtime"], e2["etag"]))
    assert _wait(settled)
    b1, b2 = _get_bytes(m1, "race", "k"), _get_bytes(m2, "race", "k")
    assert b1 == b2 and b1 in (b"AAAA", b"BBBB")
    # the survivor is the (mtime, etag)-max of the two writes
    e1 = m1._index_entry("race", "k")
    import hashlib
    etags = {hashlib.md5(b).hexdigest(): b
             for b in (b"AAAA", b"BBBB")}
    assert etags[e1["etag"]] == b1


def test_suspended_overwrite_replicates(ms):
    """Every suspended-mode overwrite reuses vid "null": the replica
    must not mistake the second overwrite for a replay of the first —
    vid-dedupe alone skipped it forever (regression)."""
    m1, m2 = ms
    vers_off = (b"<VersioningConfiguration>"
                b"<Status>Suspended</Status></VersioningConfiguration>")
    req(m1, "PUT", "/susp")
    req(m1, "PUT", "/susp?versioning", VERS_ON)
    req(m1, "PUT", "/susp?versioning", vers_off)
    req(m1, "PUT", "/susp/k", b"first")
    assert _wait(lambda: _get_bytes(m2, "susp", "k") == b"first")
    req(m1, "PUT", "/susp/k", b"second")
    assert _wait(lambda: _get_bytes(m2, "susp", "k") == b"second")

    def vids(gw):
        ent = gw._index_entry("susp", "k")
        return [v["vid"] for v in ent["versions"]] if ent else None
    assert vids(m1) == ["null"] and vids(m2) == ["null"]
    # the suspended DELETE replaces the null put with a null MARKER —
    # same vid again, and it too must replicate past the collision
    req(m1, "DELETE", "/susp/k")
    assert _wait(lambda: _get_bytes(m2, "susp", "k") is None and
                 m2._index_entry("susp", "k") is not None)
    assert m2._index_entry("susp", "k")["versions"][0]["dm"]


def test_delete_after_bumped_put_replicates(ms):
    """The del datalog record must stamp strictly after the entry it
    removed: a same-millisecond put leaves a future-bumped head mtime,
    and a wall-clock del stamp would lose the replica's newer-wins
    comparison — object deleted on the origin, kept on the replica
    forever (regression; amplified here by stamping the put 5s
    ahead)."""
    m1, m2 = ms
    req(m1, "PUT", "/dbump")
    future = time.strftime("%Y-%m-%dT%H:%M:%S",
                           time.gmtime(time.time() + 5)) + ".000Z"
    m1._now_str = lambda: future
    try:
        req(m1, "PUT", "/dbump/k", b"doomed")
    finally:
        del m1._now_str
    assert _wait(lambda: _get_bytes(m2, "dbump", "k") == b"doomed")
    req(m1, "DELETE", "/dbump/k")       # wall clock < the put's stamp
    assert _get_bytes(m1, "dbump", "k") is None
    assert _wait(lambda: _get_bytes(m2, "dbump", "k") is None)


def test_plain_put_replay_after_delete_does_not_resurrect(cluster):
    """A peer's plain-put record arriving AFTER the local delete of
    the same key must stay dead: the delete leaves a per-key tombstone
    whose stamp the late put loses to (regression: the delete removed
    the index entry outright, so the replayed put landed on an absent
    key and resurrected the object)."""
    gw = RGWGateway(cluster.rados(), pool="rgw-tomb")
    gw._create_bucket("tb")
    put = {"key": "k", "op": "put", "mode": "plain", "size": 3,
           "etag": "e1", "mtime": "2026-08-03T12:00:00.000Z",
           "trace": ["zx"]}
    assert gw.sync_apply("tb", put, b"v1!", "zx")
    shard = shard_obj("tb", gw.shard_of("tb", "k"))
    gw.io.exec(shard, "rgw", "obj_delete_plain", {"key": "k"})
    assert gw._index_entry("tb", "k") is None
    assert "k" not in gw._index("tb")   # tombstone hides from listings
    # a replay of the SAME put (another peer's re-log) must not land
    assert not gw.sync_apply("tb", put, b"v1!", "zy")
    assert gw._index_entry("tb", "k") is None
    # ... nor a different put still stamped before the delete
    older = dict(put, etag="e2", mtime="2026-08-03T12:00:00.500Z")
    assert not gw.sync_apply("tb", older, b"v2!", "zy")
    assert gw._index_entry("tb", "k") is None
    # deleting the dead key again is a clean no-op
    out = gw.io.exec(shard, "rgw", "obj_delete_plain", {"key": "k"})
    assert out["removed"] == []
    # a LOCAL put revives the key and stamps past the tombstone, so
    # replicas apply it over their own tombstones
    out = gw.io.exec(shard, "rgw", "obj_store",
                     {"key": "k", "mode": "plain", "size": 3,
                      "etag": "e3", "mtime": "2026-08-03T12:00:01.000Z",
                      "obj": ".kv3"})
    assert out["removed"] == []         # tombstone backs no object
    ent = gw._index_entry("tb", "k")
    assert ent["etag"] == "e3"
    raw = gw.io.get_omap_vals_by_keys(shard, ["k"])
    assert json.loads(raw["k"])["mtime"] > "2026-08-07"


def test_sync_del_on_absent_key_leaves_tombstone(cluster):
    """Third-zone ordering: a replicated delete can arrive BEFORE the
    put it chased.  It must leave a tombstone on the absent key so the
    late put still loses; a put strictly newer than the delete wins."""
    gw = RGWGateway(cluster.rados(), pool="rgw-tomb3")
    gw._create_bucket("tc")
    dele = {"key": "k", "op": "del",
            "mtime": "2026-08-03T12:00:01.000Z", "trace": ["zx"]}
    assert gw.sync_apply("tc", dele, None, "zx")
    assert gw._index_entry("tc", "k") is None
    assert not gw.sync_apply("tc", dele, None, "zy")     # replay
    late = {"key": "k", "op": "put", "mode": "plain", "size": 3,
            "etag": "eo", "mtime": "2026-08-03T12:00:00.900Z",
            "trace": ["zy"]}
    assert not gw.sync_apply("tc", late, b"old", "zy")
    assert gw._index_entry("tc", "k") is None
    # delete-wins-ties: an equal-stamp put was ordered before the
    # delete on the origin (datalog order), so it must lose here too
    tied = dict(late, etag="et", mtime=dele["mtime"])
    assert not gw.sync_apply("tc", tied, b"tie", "zy")
    assert gw._index_entry("tc", "k") is None
    fresh = dict(late, etag="ef", mtime="2026-08-03T12:00:01.100Z")
    assert gw.sync_apply("tc", fresh, b"new", "zy")
    assert gw._index_entry("tc", "k")["etag"] == "ef"


def test_cross_zone_delete_beats_racing_put(ms):
    """E2E resurrection window: m2 deletes a key while m1's racing
    (older-stamped) put is still in flight.  Both zones must converge
    on 'deleted' — the put record reaching m2 after its delete used to
    land on the absent key and resurrect the object on m2 only."""
    m1, m2 = ms
    req(m1, "PUT", "/tdrace")
    req(m1, "PUT", "/tdrace/k", b"v1")
    assert _wait(lambda: _get_bytes(m2, "tdrace", "k") == b"v1")
    # warm the m2->m1 pipeline on THIS bucket before the race: a
    # round-tripped delete proves m1's incremental cursor for m2's
    # tdrace log is live — otherwise the cursor gets initialized at
    # m2's CURRENT head mid-stall (full-sync floor) and would skip
    # straight past the del record the test depends on
    req(m1, "PUT", "/tdrace/warm", b"w")
    assert _wait(lambda: _get_bytes(m2, "tdrace", "warm") == b"w")
    # the warm delete itself needs m1 past its full sync of m2's
    # tdrace: full sync copies what the dump holds and never deletes,
    # so a delete that lands before m1's dump is skipped for good
    assert _wait(lambda: "tdrace" in m1.sync.markers_for(m2.sync.zone))
    req(m2, "DELETE", "/tdrace/warm")
    assert _wait(lambda: _get_bytes(m1, "tdrace", "warm") is None)
    # stall m1's OUTBOUND pulls: m2's delete stays unseen at m1 while
    # m1's racing put replicates to m2 (m1 still serves m2's pulls)
    real = m1.peer_request

    def stall(endpoint, method, path, *a, **k):
        if path == "/admin/log":
            raise urllib.error.URLError("stalled")
        return real(endpoint, method, path, *a, **k)
    m1.peer_request = stall
    try:
        req(m2, "DELETE", "/tdrace/k")  # wall-clock stamp, newest
        # m1's concurrent overwrite: forced-past stamp bumps to just
        # above v1 — strictly OLDER than m2's delete
        m1._now_str = lambda: "2000-01-01T00:00:00.000Z"
        try:
            req(m1, "PUT", "/tdrace/k", b"v2-racer")
        finally:
            del m1._now_str
        assert _get_bytes(m1, "tdrace", "k") == b"v2-racer"
        # m2 pulls the racing put and must refuse it: its tombstone
        # outranks the put's stamp
        assert _wait(lambda: m2.sync.caught_up())
        assert _get_bytes(m2, "tdrace", "k") is None
    finally:
        m1.peer_request = real
    # m1 hears the delete and drops its own racer: converged deleted
    assert _wait(lambda: _get_bytes(m1, "tdrace", "k") is None)
    assert _get_bytes(m2, "tdrace", "k") is None


def test_forwarded_master_refusal_passes_through(ms):
    """A forwarded metadata op the master answers-and-refuses must
    surface the master's real S3 error: 409 BucketNotEmpty is
    permanent, the old blanket 503 invited pointless retries
    (regression)."""
    m1, m2 = ms
    req(m1, "PUT", "/fwderr")
    assert _wait(lambda: "fwderr" in m2._buckets())
    xml = (b'<?xml version="1.0"?><Error><Code>BucketNotEmpty</Code>'
           b"<Message>fwderr</Message></Error>")
    real = m2.peer_request

    def refuse(endpoint, method, path, *a, **k):
        if method == "DELETE" and path == "/fwderr":
            raise urllib.error.HTTPError(endpoint + path, 409,
                                         "Conflict", {},
                                         _io.BytesIO(xml))
        return real(endpoint, method, path, *a, **k)
    m2.peer_request = refuse
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(m2, "DELETE", "/fwderr")
    finally:
        m2.peer_request = real
    assert ei.value.code == 409
    assert ET.fromstring(ei.value.read()).findtext("Code") == \
        "BucketNotEmpty"


def test_bucket_404_mid_round_skips_not_backoff(ms):
    """A bucket vanishing between the round's registry snapshot and
    its log fetch must skip THAT bucket only: the old peer-level
    PeerError backed off the whole (healthy) peer, stalling every
    other bucket's replication (regression)."""
    m1, m2 = ms
    req(m1, "PUT", "/gone")
    req(m1, "PUT", "/gone/k", b"g")
    req(m1, "PUT", "/alive")
    assert _wait(lambda: _get_bytes(m2, "gone", "k") == b"g")
    real = m2.peer_request

    def vanish(endpoint, method, path, *a, **k):
        body = a[0] if a else k.get("body")
        if path == "/admin/log" and body and b'"gone"' in body:
            raise urllib.error.HTTPError(endpoint + path, 404,
                                         "Not Found", {},
                                         _io.BytesIO(b"{}"))
        return real(endpoint, method, path, *a, **k)
    m2.peer_request = vanish
    try:
        req(m1, "PUT", "/alive/k", b"still-flowing")
        assert _wait(lambda:
                     _get_bytes(m2, "alive", "k") == b"still-flowing")
        # the peer stayed healthy through the 404s: no backoff state
        assert _wait(lambda: m2.sync.status()["sources"][0]["state"]
                     != "backoff" and m2.sync.caught_up())
    finally:
        m2.peer_request = real
    assert _wait(lambda: m2.sync.caught_up())


def test_versioned_same_mtime_insert_converges(cluster):
    """Concurrent same-mtime versioned puts from two zones must land
    in the SAME stack order on both sides (vid tie-break — mtime
    alone ordered them by arrival, and the two zones see opposite
    arrival orders)."""
    gw = RGWGateway(cluster.rados(), pool="rgw-tie")
    mt = "2026-08-03T12:00:00.000Z"
    a = {"key": "k", "op": "put", "mode": "enabled", "vid": "va",
         "size": 4, "etag": "ea", "mtime": mt, "trace": ["zx"]}
    b = dict(a, vid="vb", etag="eb")
    for bucket, order in (("cva", (a, b)), ("cvb", (b, a))):
        gw._create_bucket(bucket)
        for ent in order:
            assert gw.sync_apply(bucket, ent,
                                 b"dat-" + ent["vid"].encode(), "zx")
    sa = [v["vid"] for v in gw._index_entry("cva", "k")["versions"]]
    sb = [v["vid"] for v in gw._index_entry("cvb", "k")["versions"]]
    assert sa == sb == ["vb", "va"]
    # the ORIGIN's local insert bumps a same-millisecond write past
    # the head (strictly increasing per-key mtimes): sequential
    # writes keep read-your-writes, and replicas replaying the
    # origin's stamps by (mtime, vid) reproduce the same order
    gw._create_bucket("cvl")
    o1 = {"key": "k", "mode": "enabled", "vid": "va", "size": 4,
          "etag": "ea", "mtime": mt, "obj": ".x1"}
    o2 = dict(o1, vid="vb", etag="eb", obj=".x2")
    s = gw.shard_of("cvl", "k")
    for ent in (o2, o1):        # arrival order vb then va, same ms
        gw.io.exec(shard_obj("cvl", s), "rgw", "obj_store", ent)
    vers = gw._index_entry("cvl", "k")["versions"]
    assert [v["vid"] for v in vers] == ["va", "vb"]  # last write wins
    assert vers[0]["mtime"] > vers[1]["mtime"]       # bumped stamp


def test_master_bucket_delete_propagates(ms):
    """DELETE of an (empty) bucket on the master tombstones the
    registry: the secondary drops its copy, and the master's own sync
    round must NOT resurrect the bucket from the secondary's listing
    (it did, before tombstones — the client's 204 was silently
    undone)."""
    m1, m2 = ms
    req(m1, "PUT", "/bdel")
    assert _wait(lambda: "bdel" in m2._buckets())
    req(m1, "DELETE", "/bdel")
    assert "bdel" not in m1._buckets()
    assert _wait(lambda: "bdel" not in m2._buckets())
    time.sleep(0.3)             # several sync rounds
    assert "bdel" not in m1._buckets()
    assert "bdel" not in m2._buckets()
    # recreate under the same name: a fresh incarnation (new
    # "created" stamp) retires any stale cursors and full-syncs —
    # new writes must arrive on the secondary
    req(m1, "PUT", "/bdel")
    req(m1, "PUT", "/bdel/k2", b"second-life")
    assert _wait(lambda: _get_bytes(m2, "bdel", "k2") ==
                 b"second-life")


def test_datalog_auto_trim_and_lagging_peer_blocks(cluster):
    """Datalog auto-trim (ROADMAP multisite residual): a shard's .dl.
    records go once EVERY registered peer's durable cursor has passed
    them; a registered-but-lagging peer blocks the trim for exactly
    the records it still needs."""
    t1, t2 = cluster.rgw_multisite(zones=("t1", "t2"),
                                   sync_interval=0.02)
    req(t1, "PUT", "/tb")
    for i in range(6):
        req(t1, "PUT", f"/tb/k{i}", b"v%d" % i)
    assert _wait(lambda: t2.sync.caught_up())
    assert len(_dl_entries(t1, "tb")) == 6
    # the peer answers /admin/sync-markers with its DURABLE cursors;
    # durability trails the in-memory apply by up to one sync round
    # (caught_up flips before that round's _persist lands), so wait
    assert _wait(lambda: sum(
        int(m) for m in t2.sync.markers_for("t1")
        .get("tb", {"cursors": {}})["cursors"].values()) >= 6)

    # every record is behind t2's durable cursor: the trim takes all
    def _trim_converged():
        t1.sync.datalog_trim_round()
        return _dl_entries(t1, "tb") == []
    assert _wait(_trim_converged)
    assert t1.sync.datalog_trimmed >= 6

    # make t2 lag (agent stopped, zone still registered) and write on
    t2.sync.stop()
    for i in range(4):
        req(t1, "PUT", f"/tb/l{i}", b"w%d" % i)
    assert len(_dl_entries(t1, "tb")) == 4
    # the lagging peer's cursors sit below the new records: no trim
    assert t1.sync.datalog_trim_round() == 0
    assert len(_dl_entries(t1, "tb")) == 4
    # sequences never regress across a trim: the new records continue
    # past the trimmed range, so a resumed peer cannot re-read gaps
    assert min(e["seq"] for e in _dl_entries(t1, "tb")) > 0

    # incarnation guard: recreate the bucket while the peer (still
    # stopped) holds the OLD incarnation's cursors — its stale high
    # markers say nothing about the fresh datalog, so no trim
    for key in [f"k{i}" for i in range(6)] + [f"l{i}" for i in range(4)]:
        req(t1, "DELETE", f"/tb/{key}")
    req(t1, "DELETE", "/tb")
    req(t1, "PUT", "/tb")
    req(t1, "PUT", "/tb/fresh", b"new-life")
    entries = _dl_entries(t1, "tb")
    fresh = len(entries)
    assert fresh >= 1
    stale = t2.sync.markers_for("t1")["tb"]
    assert sum(int(m) for m in stale["cursors"].values()) >= 6
    # the fresh datalog restarted below the stale cursors: without
    # the incarnation check these records WOULD be trimmed
    assert min(e["seq"] for e in entries) <= max(
        int(m) for m in stale["cursors"].values())
    assert t1.sync.datalog_trim_round() == 0
    assert len(_dl_entries(t1, "tb")) == fresh


def test_registry_tombstones_pruned_after_peers_pass(ms):
    """Bounded tombstone growth (the PR 5 residual): a bucket-delete
    tombstone is pruned from BOTH zones' registries once every peer's
    sync has demonstrably passed the deletion — and never while a
    peer still holds a live pre-deletion copy (pruning then would let
    the next listing pull resurrect the bucket)."""
    import time as _t
    m1, m2 = ms
    req(m1, "PUT", "/btomb")
    assert _wait(lambda: "btomb" in m2._buckets())
    # hold m2's pull so the pre-prune state is observable: while m2
    # still lists the bucket LIVE, m1 must keep its tombstone
    # (pruning now would let m1's next listing pull resurrect the
    # bucket).  _sync_peer is stubbed (a backoff entry would be reset
    # by an in-flight round's success path), and a round-length is
    # waited out BEFORE the delete so an in-flight pull that started
    # pre-stub cannot have seen the tombstone.
    held = m2.sync._sync_peer
    m2.sync._sync_peer = lambda peer, views=None: 0
    try:
        _t.sleep(0.3)
        req(m1, "DELETE", "/btomb")
        assert "btomb" in m1._buckets_raw()
        assert "deleted" in m1._buckets_raw()["btomb"]
        _t.sleep(0.4)           # several m1 sync rounds
        assert "btomb" in m2._buckets(), \
            "hold failed: peer applied it"
        assert "btomb" in m1._buckets_raw(), \
            "tombstone pruned while the peer still held a live copy"
    finally:
        m2.sync._sync_peer = held
    # prune against a fabricated (fresh) live view is likewise a
    # no-op, and so is one whose fetch stamp PREDATES the deletion
    # (stale absence evidence must never prune)
    from ceph_tpu.cls.rgw import now_str
    live_view = {"m2": (now_str(),
                        {"btomb": {"created": "1970-01-01T00:00:00"}})}
    assert m1.prune_registry_tombstones(live_view) == 0
    stale_view = {"m2": ("1970-01-01T00:00:00.000Z", {})}
    assert m1.prune_registry_tombstones(stale_view) == 0
    # once both agents run rounds that reach every peer, the
    # tombstones drain from BOTH registries (count 0 = bounded)
    assert _wait(lambda: "btomb" not in m1._buckets_raw() and
                 "btomb" not in m2._buckets_raw())
    # and the bucket stays deleted — pruning must not resurrect
    time.sleep(0.3)
    assert "btomb" not in m1._buckets()
    assert "btomb" not in m2._buckets()
    # a recreate after the prune behaves like any fresh bucket
    req(m1, "PUT", "/btomb")
    req(m1, "PUT", "/btomb/k", b"reborn")
    assert _wait(lambda: _get_bytes(m2, "btomb", "k") == b"reborn")


def test_reserved_object_keys_rejected(ms):
    """Client objects must not collide with the index omap's
    bookkeeping namespaces — a PUT literally named `.dlmeta` would
    overwrite the shard's datalog head."""
    m1, _ = ms
    req(m1, "PUT", "/rsv")
    for key in (".dlmeta", ".dl.00000001", ".upload.deadbeef"):
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(m1, "PUT", f"/rsv/{key}", b"x")
        assert ei.value.code == 400


def test_secondary_config_ops_forward_and_survive(ms):
    """Bucket config PUT/DELETE on a secondary forwards to the master
    like bucket creation does — without the forward, the next sync
    round's master-copy adoption silently reverted the change the
    client got a 200 for."""
    m1, m2 = ms
    req(m2, "PUT", "/cfgf")
    assert _wait(lambda: "cfgf" in m1._buckets())
    req(m2, "PUT", "/cfgf?versioning", VERS_ON)
    assert m1._buckets()["cfgf"].get("versioning") == "Enabled"
    time.sleep(0.3)     # several sync rounds of master-copy adoption
    assert m2._buckets()["cfgf"].get("versioning") == "Enabled"
    # bucket DELETE forwards too: gone on both, never resurrected
    req(m2, "DELETE", "/cfgf")
    assert "cfgf" not in m1._buckets()
    time.sleep(0.3)
    assert "cfgf" not in m2._buckets()


def test_secondary_metadata_ops_forward_to_master(ms):
    """Bucket creation on the secondary lands on the master in the
    same request (forward_to_master), not a sync round later."""
    m1, m2 = ms
    req(m2, "PUT", "/fwd")
    assert "fwd" in m1._buckets()       # no sync wait: forwarded
    assert "fwd" in m2._buckets()


# ------------------------------------- kill / restart, notifications

class _Receiver:
    def __init__(self):
        self.events = []
        rec = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                rec.events.append(json.loads(body))
                self.send_response(200)
                self.send_header("Content-Length", "0")
                self.end_headers()

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.port = self.httpd.server_address[1]
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def keys(self):
        return [e["Records"][0]["s3"]["object"]["key"]
                for e in self.events]

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_kill_mid_sync_restart_resumes_from_markers(cluster):
    """The acceptance thrash: kill the secondary gateway mid-sync,
    restart it, and the agent resumes from the durable markers — full
    convergence, no duplicate applies, no re-fired notifications, no
    second full sync."""
    k1, k2 = cluster.rgw_multisite(zones=("k1", "k2"),
                                   sync_interval=0.02)
    rec = _Receiver()
    try:
        # the SAME topic name on both zones points at the receiver: a
        # replica that wrongly re-fired would be caught red-handed
        for gw in (k1, k2):
            req(gw, "POST",
                f"/?Action=CreateTopic&Name=kt&push-endpoint="
                f"http%3A%2F%2F127.0.0.1%3A{rec.port}%2F")
        req(k1, "PUT", "/kb")
        req(k1, "PUT", "/kb?notification",
            b'<NotificationConfiguration><TopicConfiguration>'
            b'<Id>n</Id><Topic>arn:aws:sns:::kt</Topic>'
            b'<Event>s3:ObjectCreated:*</Event>'
            b'</TopicConfiguration></NotificationConfiguration>')
        n = 40
        payload = {f"o{i:02d}": b"payload-%02d" % i for i in range(n)}
        for k, v in payload.items():
            req(k1, "PUT", f"/kb/{k}", v)
        # let the secondary get partway, then kill it unclean
        _wait(lambda: len(k2._index("kb")) >= 5, timeout=20)
        cluster.kill_rgw_zone(k2)
        k2b = cluster.restart_rgw_zone(k2)
        assert _wait(lambda: len(k2b._index("kb")) == n, timeout=40)
        for k, v in payload.items():
            assert _get_bytes(k2b, "kb", k) == v
        assert _wait(lambda: k2b.sync.caught_up(), timeout=40)
        # resumed incrementally from the durable markers: the fresh
        # agent never re-ran full sync ...
        assert k2b.sync.full_syncs == 0
        # ... and never re-applied a write: one datalog record per
        # object across the kill/restart, no duplicates
        puts = [e for e in _dl_entries(k2b, "kb") if e["op"] == "put"]
        assert sorted(e["key"] for e in puts) == sorted(payload)
        # the origin fired one event per object; the replica fired
        # none (zone-trace guard) — give stragglers a grace window
        assert _wait(lambda: len(rec.events) >= n, timeout=20)
        time.sleep(0.5)
        assert sorted(rec.keys()) == sorted(payload)
        # the durable marker object really is the resume point
        vals, _ = k2b.io.get_omap_vals(sync_status_obj("k1"))
        assert any(k.startswith("m.kb.") for k in vals)
    finally:
        rec.close()


def test_recreate_while_replica_down_discards_stale_content(cluster):
    """Delete + recreate a bucket while the replica sleeps: the old
    incarnation's datalog died with its bucket, so its object deletes
    can never replicate — the revived replica must DISCARD its stale
    copy and rebuild from the new incarnation, not converge to
    old ∪ new (regression: cluster-wide-deleted objects were served
    and listed there forever while sync-status said caught up)."""
    r1, r2 = cluster.rgw_multisite(zones=("r1", "r2"),
                                   sync_interval=0.02)
    req(r1, "PUT", "/rb")
    req(r1, "PUT", "/rb/old1", b"old-1")
    req(r1, "PUT", "/rb/old2", b"old-2")
    assert _wait(lambda: _get_bytes(r2, "rb", "old1") == b"old-1" and
                 _get_bytes(r2, "rb", "old2") == b"old-2")
    assert _wait(lambda: r2.sync.caught_up())
    cluster.kill_rgw_zone(r2)
    req(r1, "DELETE", "/rb/old1")
    req(r1, "DELETE", "/rb/old2")
    req(r1, "DELETE", "/rb")
    req(r1, "PUT", "/rb")                      # new incarnation
    req(r1, "PUT", "/rb/new1", b"new-1")
    r2b = cluster.restart_rgw_zone(r2)
    assert _wait(lambda: _get_bytes(r2b, "rb", "new1") == b"new-1")
    assert _wait(lambda: _get_bytes(r2b, "rb", "old1") is None and
                 _get_bytes(r2b, "rb", "old2") is None)
    assert set(r2b._index("rb")) == {"new1"}
    assert _wait(lambda: r2b.sync.caught_up())
    # both registries agree on the new incarnation's generation
    assert r2b._buckets_raw()["rb"]["created"] == \
        r1._buckets_raw()["rb"]["created"]


def test_poisoned_entry_quarantined_and_retried(cluster):
    """A datalog entry that will not apply lands in the per-shard
    error list and is retried every round — the cursor keeps moving
    past it (the reference's error_repo, not thread death)."""
    p1, p2 = cluster.rgw_multisite(zones=("p1", "p2"),
                                   sync_interval=0.02)
    orig = p2.sync_apply
    poisoned = threading.Event()
    poisoned.set()

    def wrapper(bucket, ent, data, src, **kw):
        if poisoned.is_set() and ent["key"] == "poison":
            raise RuntimeError("injected apply failure")
        return orig(bucket, ent, data, src, **kw)
    p2.sync_apply = wrapper

    req(p1, "PUT", "/pz")
    req(p1, "PUT", "/pz/ok1", b"one")
    req(p1, "PUT", "/pz/poison", b"toxic")
    req(p1, "PUT", "/pz/ok2", b"two")
    # the healthy entries apply; the cursor moved past the poison
    assert _wait(lambda: _get_bytes(p2, "pz", "ok1") == b"one" and
                 _get_bytes(p2, "pz", "ok2") == b"two")
    assert _get_bytes(p2, "pz", "poison") is None
    assert _wait(lambda: len(p2.sync.error_list()) == 1)
    rec = p2.sync.error_list()[0]
    assert rec["entry"]["key"] == "poison" and rec["bucket"] == "pz"
    assert "injected apply failure" in rec["err"]
    # it is RETRIED, not parked: the retry counter climbs
    assert _wait(lambda: p2.sync.error_list()[0]["retries"] >= 2)
    st = [s for s in p2.sync.status()["sources"]
          if s["source"] == "p1"][0]
    assert st["errors"] == 1 and not st["caught_up"]
    # the error list is durable (a restart would retry it too)
    assert _wait(lambda: any(
        k.startswith("e.pz.") and json.loads(v)
        for k, v in p2.io.get_omap_vals(
            sync_status_obj("p1"))[0].items()))
    # lift the poison: the retry drains the list and converges
    poisoned.clear()
    assert _wait(lambda: _get_bytes(p2, "pz", "poison") == b"toxic")
    assert _wait(lambda: not p2.sync.error_list())
    assert _wait(lambda: p2.sync.caught_up())


# ------------------------------------------------------ CLI satellite

def test_rados_cli_rgw_verbs(cluster, ms):
    m1, m2 = ms
    out = _io.StringIO()
    rc = rados_cli.main(["rgw", "period", "get", "--pool", "rgw-m1"],
                        rados=cluster.rados(), out=out)
    assert rc == 0
    period = json.loads(out.getvalue())
    assert period["realm"] == "gold" and period["epoch"] >= 1
    out = _io.StringIO()
    rc = rados_cli.main(
        ["rgw", "sync-status", "--endpoint",
         f"http://127.0.0.1:{m2.port}"],
        rados=cluster.rados(), out=out)
    assert rc == 0
    txt = out.getvalue()
    assert "zone m2" in txt and "source m1:" in txt
    out = _io.StringIO()
    rc = rados_cli.main(
        ["rgw", "datalog", "status", "dlc", "--pool", "rgw-m1",
         "--shards", "4"],
        rados=cluster.rados(), out=out)
    assert rc == 0 and "head" in out.getvalue()
    # unknown verb shapes fail with usage, not a traceback
    assert rados_cli.main(["rgw", "realm", "frob"],
                          rados=cluster.rados(),
                          out=_io.StringIO()) == 1


# ------------------------------------------------- keystone satellite

class _KeystoneStub:
    """Stub keystone: GET /v3/auth/tokens validates X-Subject-Token
    against a token table (the test's 'external identity service')."""

    def __init__(self, tokens):
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_GET(self):
                tok = self.headers.get("X-Subject-Token", "")
                if self.path != "/v3/auth/tokens" or \
                        tok not in stub.tokens:
                    self.send_response(404)
                    self.end_headers()
                    return
                body = json.dumps(
                    {"token": stub.tokens[tok]}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.tokens = tokens
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture()
def keystone():
    ks = _KeystoneStub({
        "tok-good": {"user": {"name": "alice"}},
        "tok-expired": {"user": {"name": "bob"},
                        "expires_at": time.time() - 5},
        "tok-iso": {"user": {"name": "carol"},
                    "expires_at": "2099-01-01T00:00:00Z"}})
    yield ks
    ks.close()


def test_amz_date_parses_utc_under_dst_tz():
    """x-amz-date is UTC: parsing it through mktime applied the
    host's DST offset, skewing every signed request — including all
    peer sync traffic between secured zones — by 3600s for half the
    year (regression)."""
    import calendar
    import os
    from ceph_tpu.rgw.auth import _parse_amz_date
    old = os.environ.get("TZ")
    os.environ["TZ"] = "America/New_York"     # observes DST in July
    time.tzset()
    try:
        assert _parse_amz_date("20260715T120000Z") == \
            calendar.timegm((2026, 7, 15, 12, 0, 0, 0, 0, 0))
    finally:
        if old is None:
            os.environ.pop("TZ", None)
        else:
            os.environ["TZ"] = old
        time.tzset()


def test_keystone_engine_validation(keystone):
    eng = KeystoneEngine(keystone.url)
    assert eng.validate("tok-good") == "alice"
    assert eng.validate("tok-iso") == "carol"
    with pytest.raises(KeystoneError) as ei:
        eng.validate("tok-unknown")
    assert ei.value.status == 401
    with pytest.raises(KeystoneError) as ei:
        eng.validate("")
    assert ei.value.status == 401
    # expired token is EACCES (403), not merely invalid
    with pytest.raises(KeystoneError) as ei:
        eng.validate("tok-expired")
    assert ei.value.status == 403 and ei.value.code == "AccessDenied"
    # keystone down -> 503, never a free pass
    keystone.close()
    with pytest.raises(KeystoneError) as ei:
        eng.validate("tok-never-seen")
    assert ei.value.status == 503


def test_keystone_cache_still_enforces_expiry(keystone):
    """A cached acceptance must not outlive the token: expiry is
    checked on every use, cache hit or not."""
    keystone.tokens["tok-brief"] = {"user": {"name": "dave"},
                                    "expires_at": time.time() + 0.6}
    eng = KeystoneEngine(keystone.url)
    assert eng.validate("tok-brief") == "dave"   # cached now
    time.sleep(0.8)
    with pytest.raises(KeystoneError) as ei:
        eng.validate("tok-brief")                # cache hit, expired
    assert ei.value.status == 403


def test_keystone_gateway_config_gated(cluster, keystone):
    g = RGWGateway(cluster.rados(), pool="ksgw",
                   keystone_url=keystone.url)
    g.start()
    try:
        st, _, _ = req(g, "PUT", "/ksb",
                       headers={"X-Auth-Token": "tok-good"})
        assert st == 200
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(g, "PUT", "/ksb2",
                headers={"X-Auth-Token": "tok-expired"})
        assert ei.value.code == 403
        assert b"AccessDenied" in ei.value.read()
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(g, "PUT", "/ksb2",
                headers={"X-Auth-Token": "tok-bogus"})
        assert ei.value.code == 401
        # keystone as the ONLY engine: a token-less request fails
        # closed instead of falling back to anonymous
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(g, "PUT", "/ksb3")
        assert ei.value.code == 401
    finally:
        g.shutdown()
    # config-gated: a gateway WITHOUT keystone_url ignores the header
    g2 = RGWGateway(cluster.rados(), pool="ksgw2")
    g2.start()
    try:
        st, _, _ = req(g2, "PUT", "/anon",
                       headers={"X-Auth-Token": "tok-bogus"})
        assert st == 200
    finally:
        g2.shutdown()


def test_keystone_only_multisite_replicates(cluster, keystone, capsys):
    """Two keystone-secured zones (no keyring): sync traffic signs
    SigV4 as the system user and carries no token, so the auth gate
    must verify that signature instead of failing it closed as
    token-less — or a keystone-secured zone never receives a byte of
    sync traffic (regression).  Also drives `rados rgw sync-status`
    both unsigned (refused, not 'unreachable') and signed."""
    from ceph_tpu.rgw.auth import sign_request
    k1, k2 = cluster.rgw_multisite(
        zones=("ks1", "ks2"), zonegroup="kszg", realm="ksr",
        keystone_url=keystone.url, system_key=("sys-ak", "sys-sk"))
    tok = {"X-Auth-Token": "tok-good"}

    def get(gw, path):
        try:
            return req(gw, "GET", path, headers=dict(tok))[2]
        except urllib.error.HTTPError:
            return None
    try:
        st, _, _ = req(k1, "PUT", "/ksms", headers=dict(tok))
        assert st == 200
        req(k1, "PUT", "/ksms/k", b"ks-bytes", headers=dict(tok))
        assert _wait(lambda: get(k2, "/ksms/k") == b"ks-bytes")
        assert _wait(lambda: k2.sync.caught_up() and
                     k1.sync.caught_up())
        # wrong system secret is refused, not silently accepted
        bad = sign_request("GET", "/", {"host": f"127.0.0.1:{k1.port}"},
                           b"", "sys-ak", "wrong-sk")
        with pytest.raises(urllib.error.HTTPError) as ei:
            req(k1, "GET", "/", headers=bad)
        assert ei.value.code == 403
        # the CLI against the secured admin surface: unsigned is a
        # REFUSAL (the old message claimed the gateway was down)...
        ep = f"http://127.0.0.1:{k2.port}"
        assert rados_cli.main(
            ["rgw", "sync-status", "--endpoint", ep],
            rados=cluster.rados(), out=_io.StringIO()) == 1
        assert "gateway refused" in capsys.readouterr().err
        # ...and signing with the system key reads the live status
        buf = _io.StringIO()
        assert rados_cli.main(
            ["rgw", "sync-status", "--endpoint", ep,
             "--access", "sys-ak", "--secret", "sys-sk"],
            rados=cluster.rados(), out=buf) == 0
        assert "ks1" in buf.getvalue()
        # kill + restart: the revived gateway keeps its security
        # config — an anonymous restart would have every signed pull
        # refused by its peers and replication would never resume
        cluster.kill_rgw_zone(k2)
        k2 = cluster.restart_rgw_zone(k2)
        assert k2.system_key == ("sys-ak", "sys-sk")
        assert k2.keystone is not None
        req(k1, "PUT", "/ksms/k2", b"after-restart", headers=dict(tok))
        assert _wait(lambda: get(k2, "/ksms/k2") == b"after-restart")
        assert _wait(lambda: k2.sync.caught_up())
    finally:
        for g in (k1, k2):
            g.shutdown()
            if g in cluster.rgws:
                cluster.rgws.remove(g)


def test_forwarded_create_adopts_master_stamp(ms):
    """Bucket creation forwarded from a secondary must adopt the
    master's created stamp: independently-stamped registries would
    make the incarnation guard (sync_reset_bucket) treat the SAME
    bucket as two generations and discard fresh local content
    (regression)."""
    m1, m2 = ms
    req(m2, "PUT", "/fwdstamp")             # forwarded to master m1
    assert _wait(lambda: "fwdstamp" in m1._buckets() and
                 "fwdstamp" in m2._buckets())
    assert m1._buckets_raw()["fwdstamp"]["created"] == \
        m2._buckets_raw()["fwdstamp"]["created"]
    # a server-side copy whose SOURCE is a bookkeeping key is a
    # clean 404, not a handler crash
    req(m1, "PUT", "/fwdstamp/ok", b"ok")
    with pytest.raises(urllib.error.HTTPError) as ei:
        req(m1, "PUT", "/fwdstamp/copy", headers={
            "x-amz-copy-source": "/fwdstamp/.dlmeta"})
    assert ei.value.code == 404


def test_reserved_key_reads_are_clean_404(ms):
    """GET/HEAD of a bookkeeping key must be a clean NoSuchKey: the
    index record behind `.dlmeta` has no etag/size, so serving it
    crashed the handler (HEAD) or 500'd (GET) instead of 404ing
    (regression; the write side already rejects 400)."""
    m1, _ = ms
    req(m1, "PUT", "/resk")
    req(m1, "PUT", "/resk/x", b"x")     # seeds .dlmeta on a shard
    for key in (".dlmeta", ".dl.0000000000000001", ".upload.dead"):
        for method in ("GET", "HEAD"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                req(m1, method, f"/resk/{key}")
            assert ei.value.code == 404, (method, key)
    with pytest.raises(urllib.error.HTTPError) as ei:
        req(m1, "PUT", "/resk/.dlmeta", b"z")
    assert ei.value.code == 400


def test_synth_retry_applies_real_source_state(ms):
    """A quarantined synthesizer failure retries against the key's
    CURRENT state at the source: the old fabricated plain-put stub
    (no mtime/etag) either applied corrupt metadata or silently
    drained without syncing (regression)."""
    from ceph_tpu.client.rados import RadosError
    from ceph_tpu.rgw.gateway import _index_obj
    m1, m2 = ms
    req(m1, "PUT", "/synthb")
    req(m1, "PUT", "/synthb/k", b"real-bytes")
    assert _wait(lambda: _get_bytes(m2, "synthb", "k") == b"real-bytes")
    assert _wait(lambda: m2.sync.caught_up())
    ep = f"http://127.0.0.1:{m1.port}"
    # a key that vanished at the source drains (0 applied, no crash)
    ghost = {"key": "ghost", "op": "synth", "vid": None, "trace": []}
    assert m2.sync._apply("m1", ep, "synthb", ghost) == 0
    # surgically lose m2's index entry (offline-surgery style), then
    # retry the synth record: the REAL state comes back, with the
    # origin's metadata — not empty-string mtime/etag
    for s in range(m2._nshards("synthb")):
        try:
            m2.io.remove_omap_keys(_index_obj("synthb", s), ["k"])
        except RadosError:
            pass
    assert _get_bytes(m2, "synthb", "k") is None
    ent = {"key": "k", "op": "synth", "vid": None, "trace": []}
    assert m2.sync._apply("m1", ep, "synthb", ent) == 1
    restored = m2._index_entry("synthb", "k")
    assert restored["etag"] and restored["mtime"]
    assert restored["etag"] == m1._index_entry("synthb", "k")["etag"]
    assert _get_bytes(m2, "synthb", "k") == b"real-bytes"
    # an already-synced key is an idempotent skip on retry
    assert m2.sync._apply("m1", ep, "synthb", ent) == 0


def test_datalog_head_probe_returns_no_entries(ms):
    """max=0 is the head-probe contract (DataLog.head, the pre-dump
    head capture in full sync): it must ship ZERO entries, not one —
    the limit check ran after the append (regression)."""
    m1, _ = ms
    req(m1, "PUT", "/dlh")
    req(m1, "PUT", "/dlh/k", b"x")
    dl = DataLog(m1.io)
    heads = 0
    for s in range(m1._nshards("dlh")):
        ents, head = dl.list("dlh", s, 0, 0)
        assert ents == []
        heads += head
    assert heads >= 1           # the put IS in some shard's log


def test_zero_peer_datalog_trims_by_age_respecting_fullsync(cluster):
    """Zero-peer residual (ROADMAP): a zone with NO registered peers
    has no cursors to trim behind, so its datalog ages out instead —
    bounded per round, and never past an in-flight full-sync floor
    (a peer that just pulled the bucket index dump starts its
    incremental cursors at the dump-time heads)."""
    solo, = cluster.rgw_multisite(zones=("solo",), zonegroup="zgsolo",
                                  realm="lone", sync_interval=0.5)
    assert solo.multisite.peers() == []
    req(solo, "PUT", "/ab")
    for i in range(6):
        req(solo, "PUT", f"/ab/k{i}", b"v%d" % i)
    first = _dl_entries(solo, "ab")
    assert len(first) == 6
    # entries are younger than the age bar: nothing trims
    assert solo.sync.datalog_trim_round() == 0
    assert len(_dl_entries(solo, "ab")) == 6

    # an in-flight full sync (the bucket index dump) floors the trim:
    # records past the dump-time heads must survive any aging
    assert json.loads(req(solo, "GET", "/admin/bucket?name=ab")[2])
    floors = solo.fullsync_floor("ab")
    assert floors and sum(floors.values()) >= 6
    for i in range(3):
        req(solo, "PUT", f"/ab/post{i}", b"p%d" % i)
    time.sleep(0.15)
    solo.sync.NOPEER_MAX_AGE_S = 0.1        # everything now "old"
    assert _wait(lambda: (solo.sync.datalog_trim_round() or True) and
                 len(_dl_entries(solo, "ab")) == 3)
    # exactly the pre-dump records went; the post-dump ones survived
    left = {e["key"] for e in _dl_entries(solo, "ab")}
    assert left == {f"post{i}" for i in range(3)}

    # grace expiry releases the floor: the rest ages out too, still
    # bounded per shard per round
    solo.FULLSYNC_GRACE_S = 0.0
    assert solo.fullsync_floor("ab") is None
    solo.sync.NOPEER_TRIM_MAX = 1
    assert _wait(lambda: (solo.sync.datalog_trim_round() or True) and
                 _dl_entries(solo, "ab") == [])
    assert solo.sync.datalog_trimmed >= 9
