"""Chaos harness: ChaosRunner schedules + the cluster behaviors the
FaultPlane surfaces — asymmetric mon partitions (the elector
counter-candidacy/late-ack bugs), and RGW multisite mid-sync
partitions (backoff + durable-cursor safety) (ISSUE 17)."""
import time
import urllib.error
import urllib.request

import pytest

from ceph_tpu.testing import ChaosRunner, MiniCluster


def _mk(n_osd=4, n_mon=3, fault_seed=7):
    c = MiniCluster(n_osd=n_osd, threaded=False, n_mon=n_mon,
                    fault_seed=fault_seed)
    c.pump()
    c.wait_all_up()
    return c


# ------------------------------------------------- mon election chaos
def test_asymmetric_partition_quorum_excludes_half_blind_mon():
    """mon.2 goes half-blind: it can SEND but receives nothing.  The
    majority must re-form a [0, 1] quorum, mon.2's paced candidacies
    must not duel it into election churn, and the heal must readmit
    mon.2 cleanly.  Regression for two chaos-surfaced elector bugs:
    counter-candidacy sent only to the (unreachable) proposer wedged
    the majority until the lease timeout; and a victory-racing late
    ack left a mon a lease-fed peon outside the quorum forever."""
    c = _mk(n_osd=3)
    try:
        assert (c.leader() or c.mons[0]).quorum() == [0, 1, 2]
        # a -> b blocked only: mon.2 is deaf, not mute
        ids = c.network.faults.partition(
            ["mon.0", "mon.1"], ["mon.2"], symmetric=False)
        # the fake clock must run ahead of the real one that stamped
        # set-up, whatever the host's uptime
        now = time.monotonic() + 50_000.0
        epochs = []
        for i in range(10):
            now += 11.0
            c.tick(now)
            ldr = c.leader()
            if i >= 3:
                # majority stable: leader 0, quorum excludes mon.2,
                # and elections are not dueling between ticks
                assert ldr is not None and ldr.rank == 0, i
                assert ldr.quorum() == [0, 1], (i, ldr.quorum())
            epochs.append(c.mons[0].elector.epoch)
        # mon.2's candidacies are paced by the election backoff: a
        # bounded trickle of epochs, not one (or more) per tick
        assert epochs[-1] - epochs[0] <= 2 * len(epochs), epochs
        rc, _, h = c.leader().handle_command({"prefix": "health"})
        assert "MON_DOWN" in h["checks"]
        # heal: mon.2's next paced candidacy readmits it
        c.network.faults.heal(ids)
        for i in range(20):
            now += 11.0
            c.tick(now)
            ldr = c.leader()
            if ldr is not None and ldr.quorum() == [0, 1, 2]:
                break
        else:
            pytest.fail(f"mon.2 never rejoined: "
                        f"{ldr.quorum() if ldr else None}")
        rc, _, h = c.leader().handle_command({"prefix": "health"})
        assert "MON_DOWN" not in h["checks"]
    finally:
        c.shutdown()


def test_late_ack_expands_quorum_instead_of_stranding_voter():
    """Startup itself races acks against the majority win; with the
    expansion fix the very first settled quorum holds every mon."""
    c = _mk(n_osd=3)
    try:
        ldr = c.leader()
        assert ldr is not None and ldr.quorum() == [0, 1, 2]
    finally:
        c.shutdown()


# ------------------------------------------------- ChaosRunner schedules
SCHEDULE = [
    {"at": 20.0, "action": "partition", "a": ["mon.2"],
     "b": ["mon.0", "mon.1"], "label": "mon-minority"},
    {"at": 60.0, "action": "heal", "target": "mon-minority"},
    {"at": 80.0, "action": "kill_osd", "osd": 3},
    {"at": 120.0, "action": "revive_osd", "osd": 3},
    {"at": 140.0, "action": "drop", "src": "osd.*", "dst": "osd.*",
     "p": 0.02, "types": ["Ping"], "label": "ping-loss"},
    {"at": 200.0, "action": "heal", "target": "ping-loss"},
]


def _run_schedule(fault_seed=7):
    c = _mk(n_osd=5)
    try:
        return ChaosRunner(c, SCHEDULE, rados=c.rados(), seed=1).run()
    finally:
        c.shutdown()


def test_chaos_schedule_invariants_and_replay_digest():
    """The regression schedule for the elector fixes: mon-minority
    partition + OSD flap + heartbeat loss under live IO.  run()
    raises InvariantViolation unless quorum re-forms, PGs go
    active+clean, acked writes read back, health/SLOW_OPS clear and
    the crash table stays empty — and the fault sequence must replay
    byte-identically from the seed."""
    rep1 = _run_schedule()
    assert rep1["acked"] == rep1["ops_total"] > 0
    assert rep1["fault_counts"].get("partition", 0) > 0
    phases = {p["phase"] for p in rep1["phases"]}
    assert "mon-minority" in phases
    rep2 = _run_schedule()
    assert rep2["fault_digest"] == rep1["fault_digest"]
    assert rep2["fault_counts"] == rep1["fault_counts"]


def test_isolate_primary_mid_write_recovers():
    """Cut the acting primary of a known object off the network
    mid-run; the mon must detect it via heartbeat silence, remap, and
    every acked write must survive the heal."""
    c = _mk(n_osd=5)
    try:
        r = c.rados()
        r.pool_create("chaos", pg_num=16)
        c.pump()
        sched = [
            {"at": 15.0, "action": "isolate_primary",
             "oid": "chaos_00001", "label": "primary-cut"},
            {"at": 75.0, "action": "heal", "target": "primary-cut"},
        ]
        rep = ChaosRunner(c, sched, rados=r, seed=3).run()
        assert rep["fault_counts"].get("partition", 0) > 0
        assert rep["acked"] > 0
    finally:
        c.shutdown()


# --------------------------------------------- rgw multisite partition
def _req(gw, method, path, data=None):
    r = urllib.request.Request(
        f"http://127.0.0.1:{gw.port}{path}", data=data, method=method)
    with urllib.request.urlopen(r, timeout=30) as resp:
        return resp.status, resp.read()


def _wait(cond, timeout=30.0, interval=0.05):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def test_rgw_multisite_mid_sync_partition():
    """Partition the secondary from the master mid-sync: the sync
    agent's shared Backoff must engage (paced retries, not a tight
    loop), durable cursors must NOT advance past unapplied entries,
    and the lag must drain after the heal."""
    c = MiniCluster(n_osd=4, threaded=True)
    c.wait_all_up()
    try:
        gw1, gw2 = c.rgw_multisite(zones=("c1", "c2"))
        _req(gw1, "PUT", "/pb")
        _req(gw1, "PUT", "/pb/before", b"pre-partition" * 10)
        assert _wait(gw2.sync.caught_up), gw2.sync.status()
        markers_before = gw2.sync.markers_for("c1")
        # sever the secondary's pulls from the master (HTTP plane)
        ids = c.network.faults.partition(["rgw.c2"], ["rgw.c1"])
        _req(gw1, "PUT", "/pb/during", b"mid-partition" * 20)
        # the shared Backoff engages: consecutive failures climb and
        # status reports the source as backing off
        assert _wait(lambda: (bo := gw2.sync._backoff.get("c1"))
                     is not None and bo.failures >= 2), \
            gw2.sync.status()
        src_rows = {s["source"]: s for s in
                    gw2.sync.status()["sources"]}
        assert src_rows["c1"]["state"] == "backoff", src_rows
        # durable cursors stayed put: nothing advanced past entries
        # that never applied (trim safety), and the object is absent
        assert gw2.sync.markers_for("c1") == markers_before
        with pytest.raises(urllib.error.HTTPError):
            _req(gw2, "GET", "/pb/during")
        # heal: lag drains, bytes converge, backoff resets
        c.network.faults.heal(ids)
        assert _wait(gw2.sync.caught_up), gw2.sync.status()
        assert _req(gw2, "GET", "/pb/during")[1] == \
            b"mid-partition" * 20
        assert gw2.sync._backoff["c1"].failures == 0
    finally:
        c.shutdown()
