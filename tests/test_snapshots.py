"""Pool snapshots: COW clones, snap reads, rollback, recovery of
clones (ref: pg_pool_t snap_seq/snaps; PrimaryLogPG::make_writeable /
_rollback_to; OSDMonitor 'osd pool mksnap')."""
import pytest

from ceph_tpu.client import RadosError, WriteOp
from ceph_tpu.testing import MiniCluster


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osd=4, threaded=True)
    c.wait_all_up()
    r = c.rados()
    r.pool_create("sp", pg_num=8)
    r.mon_command({"prefix": "osd erasure-code-profile set",
                   "name": "k2m1",
                   "profile": {"plugin": "tpu", "k": "2", "m": "1",
                               "crush-failure-domain": "osd"}})
    r.pool_create("esp", pg_num=8, pool_type="erasure",
                  erasure_code_profile="k2m1")
    yield c, r
    c.shutdown()


@pytest.fixture()
def io(cluster):
    _, r = cluster
    return r.open_ioctx("sp")


def test_mksnap_rmsnap_commands(io):
    io.snap_create("alpha")
    snaps = io.list_pool_snaps()
    assert "alpha" in snaps.values()
    with pytest.raises(RadosError):
        io.snap_create("alpha")          # EEXIST
    io.snap_remove("alpha")
    assert "alpha" not in io.list_pool_snaps().values()
    with pytest.raises(RadosError):
        io.snap_remove("alpha")          # ENOENT


def test_ec_pool_refuses_snaps(cluster):
    _, r = cluster
    e = r.open_ioctx("esp")
    with pytest.raises(RadosError):
        e.snap_create("nope")


def test_cow_and_snap_reads(io):
    oid = "cowobj"
    io.write_full(oid, b"version-one")
    io.snap_create("s1")
    s1 = io.snap_lookup("s1")
    io.write_full(oid, b"version-two is longer")
    io.snap_create("s2")
    s2 = io.snap_lookup("s2")
    io.write_full(oid, b"v3")
    # head and both snapshots readable independently
    assert io.read(oid) == b"v3"
    assert io.read(oid, snapid=s1) == b"version-one"
    assert io.read(oid, snapid=s2) == b"version-two is longer"
    ls = io.list_snaps(oid)
    assert ls["head_exists"]
    assert sorted(int(t) for t in ls["clones"]) == [s1, s2]


def test_snap_of_unmodified_object_reads_head(io):
    oid = "lazy"
    io.write_full(oid, b"unchanged")
    io.snap_create("s-l")
    sid = io.snap_lookup("s-l")
    # no write since the snap: served from head, no clone exists
    assert io.read(oid, snapid=sid) == b"unchanged"
    assert io.list_snaps(oid)["clones"] == {}


def test_object_created_after_snap_absent_at_snap(io):
    io.snap_create("s-pre")
    sid = io.snap_lookup("s-pre")
    io.write_full("newborn", b"late")
    io.write_full("newborn", b"later")   # forces a clone decision
    with pytest.raises(RadosError, match="ENOENT"):
        io.read("newborn", snapid=sid)


def test_delete_preserves_snapshots(io):
    oid = "ghost"
    io.write_full(oid, b"will be deleted")
    io.snap_create("s-g")
    sid = io.snap_lookup("s-g")
    io.remove(oid)
    with pytest.raises(RadosError, match="ENOENT"):
        io.read(oid)
    assert io.read(oid, snapid=sid) == b"will be deleted"


def test_rollback(io):
    oid = "rb"
    io.operate(oid, WriteOp().write_full(b"good state")
               .set_xattr("tag", b"good").set_omap({"k": b"good"}))
    io.snap_create("s-rb")
    io.operate(oid, WriteOp().write_full(b"bad state!")
               .set_xattr("tag", b"bad").set_omap({"k": b"bad"}))
    io.snap_rollback(oid, "s-rb")
    assert io.read(oid) == b"good state"
    assert io.get_xattr(oid, "tag") == b"good"
    assert io.get_omap_vals(oid)[0] == {"k": b"good"}
    # rollback of a post-snap object removes it
    io.snap_create("s-rb2")
    io.write_full("rb-new", b"x")
    io.write_full("rb-new", b"y")
    io.snap_rollback("rb-new", "s-rb2")
    with pytest.raises(RadosError, match="ENOENT"):
        io.read("rb-new")


def test_removed_snap_never_resurrects(io):
    """A lagging client's snapc must not re-create clones for a
    deleted snapshot (pool removed_snaps filtering,
    ref: pg_pool_t::removed_snaps)."""
    oid = "zombie"
    io.write_full(oid, b"content")
    io.snap_create("doomed")
    sid = io.snap_lookup("doomed")
    io.snap_remove("doomed")
    # lagging client: sends the stale snapc by hand
    io.set_write_snapc(sid, [sid])
    try:
        io.write_full(oid, b"after removal")
    finally:
        io.write_snapc = None
    assert io.list_snaps(oid)["clones"] == {}
    with pytest.raises(RadosError, match="ENOENT"):
        io.read(oid, snapid=sid)


def test_write_cows_with_lagging_osd_map():
    """The client's SnapContext rides with the write: even when the
    primary's map hasn't caught up with a fresh snapshot, the COW
    still happens (ref: MOSDOp's snapc)."""
    c = MiniCluster(n_osd=3, threaded=True)
    try:
        c.wait_all_up()
        r = c.rados()
        r.pool_create("lp", pg_num=8)
        io = r.open_ioctx("lp")
        from ceph_tpu.msg.messages import MMap
        oid = "lagobj"
        io.write_full(oid, b"pre-snap state")
        # freeze map delivery to OSDs, then take the snap (the client
        # sees it; the OSDs don't)
        c.network.filter = lambda src, dst, msg: not (
            dst.startswith("osd.") and isinstance(msg, MMap))
        try:
            io.snap_create("s-lag")
            sid = io.snap_lookup("s-lag")
            io.write_full(oid, b"post-snap state")
        finally:
            c.network.filter = None
        assert io.read(oid, snapid=sid) == b"pre-snap state"
        assert io.read(oid) == b"post-snap state"
    finally:
        c.shutdown()


def test_clones_survive_recovery(cluster, io):
    """A newcomer receiving recovery pushes gets the clones too, and
    snap reads keep working after the old holder is gone."""
    c, r = cluster
    oid = "snapdur"
    io.write_full(oid, b"snapshotted data")
    io.snap_create("s-dur")
    sid = io.snap_lookup("s-dur")
    io.write_full(oid, b"newer data")
    pid = r.pool_lookup("sp")
    m = r.objecter.osdmap
    raw = m.object_locator_to_pg(oid, pid)
    _, _, acting, primary = m.pg_to_up_acting_osds(raw)
    victim = next(o for o in acting if o != primary)
    e0 = m.epoch
    r.mon_command({"prefix": "osd out", "ids": [victim]})
    r.objecter.wait_for_map(e0 + 1)
    import time
    deadline = time.monotonic() + 20
    moved = False
    while time.monotonic() < deadline and not moved:
        m2 = r.objecter.osdmap
        _, _, acting2, _ = m2.pg_to_up_acting_osds(raw)
        newcomer = [o for o in acting2 if o not in acting and o >= 0]
        if newcomer:
            pg = m2.pools[pid].raw_pg_to_pg(raw)
            st = c.osds[newcomer[0]].pgs.get(pg)
            if st is not None and st.shard is not None and \
                    st.shard.clone_tags(oid):
                moved = True
        time.sleep(0.1)
    assert moved, "newcomer never received the clones"
    assert io.read(oid, snapid=sid) == b"snapshotted data"
    assert io.read(oid) == b"newer data"
    e1 = r.objecter.osdmap.epoch
    r.mon_command({"prefix": "osd in", "ids": [victim]})
    r.objecter.wait_for_map(e1 + 1)
    # the module's cluster is shared: let every OSD take the map and
    # finish moving the PGs back before the next test touches them
    deadline = time.monotonic() + 20
    while any(d.osdmap.epoch < r.objecter.osdmap.epoch
              or d.pgs_recovering() for d in c.osds.values()):
        assert time.monotonic() < deadline, "PGs never settled"
        time.sleep(0.1)


def test_scrub_detects_clone_divergence(cluster, io):
    c, r = cluster
    oid = "scrubsnap"
    io.write_full(oid, b"snap me")
    io.snap_create("s-sc")
    io.write_full(oid, b"head now")
    pid = r.pool_lookup("sp")
    m = r.objecter.osdmap
    raw = m.object_locator_to_pg(oid, pid)
    pg = m.pools[pid].raw_pg_to_pg(raw)
    _, _, acting, primary = m.pg_to_up_acting_osds(raw)
    victim = next(o for o in acting if o != primary)
    # corrupt the replica's clone
    sid = io.snap_lookup("s-sc")
    from ceph_tpu.osd.ec_backend import pg_cid
    from ceph_tpu.store import ObjectId, Transaction
    c.osds[victim].store.queue_transaction(Transaction().write(
        pg_cid(pg), ObjectId(oid, snap=sid), 0, b"EVIL"))
    res = r.pg_scrub(pid, pg.ps)
    assert oid in res["inconsistent"]
    res2 = r.pg_scrub(pid, pg.ps, repair=True)
    assert res2["repaired"] >= 1
    res3 = r.pg_scrub(pid, pg.ps)
    assert res3["inconsistent"] == []
    assert io.read(oid, snapid=sid) == b"snap me"
