"""The Objecter's per-epoch PG-target table: every op's target equals a
cold OSDMap evaluation of the current map, across every kind of map
change, and CRUSH runs once per PG per epoch
(ref: src/osdc/Objecter.cc:1095 _calc_target, :1182 handle_osd_map)."""
import copy

import pytest

from ceph_tpu.client.objecter import Objecter
from ceph_tpu.common.perf_counters import global_perf
from ceph_tpu.crush import mapper
from ceph_tpu.crush.types import (
    CRUSH_RULE_CHOOSELEAF_INDEP, CRUSH_RULE_EMIT, CRUSH_RULE_TAKE,
    CrushRule, CrushRuleMask, CrushRuleStep,
)
from ceph_tpu.msg.messages import MMap, OSDOpReply
from ceph_tpu.msg.messenger import LocalNetwork
from ceph_tpu.osd.osdmap import CEPH_OSD_OUT, Incremental, OSDMap
from ceph_tpu.osd.types import PG, PGPool, POOL_TYPE_ERASURE

REP, EC = 0, 1


def make_map() -> OSDMap:
    """13 hosts of one OSD each: a replicated pool (size 3, firstn)
    and a k=8 m=4 indep EC pool, both with pgp_num < pg_num so the
    placement seed folds differently from the PG."""
    m = OSDMap()
    m.build_simple(13, PGPool(pg_num=24, pgp_num=20), osds_per_host=1)
    root = next(b.id for b in m.crush.buckets
                if b is not None and b.type == 10)
    m.crush.rules.append(CrushRule(
        steps=[CrushRuleStep(CRUSH_RULE_TAKE, root),
               CrushRuleStep(CRUSH_RULE_CHOOSELEAF_INDEP, 12, 1),
               CrushRuleStep(CRUSH_RULE_EMIT)],
        mask=CrushRuleMask(ruleset=1, type=POOL_TYPE_ERASURE,
                           min_size=1, max_size=16)))
    m.pools[EC] = PGPool(type=POOL_TYPE_ERASURE, size=12, min_size=9,
                         crush_rule=1, pg_num=12, pgp_num=10)
    m.pool_names[EC] = "ecpool"
    return m


def make_objecter(m: OSDMap):
    """An unstarted Objecter on map `m` whose sends are recorded
    (tid -> osd of the newest send) instead of going to the wire."""
    o = Objecter(LocalNetwork())
    sent: dict[int, int] = {}
    o._send_op = lambda op: sent.__setitem__(op.tid, op.target_osd)
    o._handle_map(MMap(full_map=copy.deepcopy(m)))
    return o, sent


def install(o: Objecter, inc: Incremental) -> None:
    o._handle_map(MMap(incrementals=[copy.deepcopy(inc)]))


def map_changes(m: OSDMap, pgs: dict) -> list:
    """One incremental per kind of map change, in epoch order.  `pgs`
    holds a few folded PGs of each pool that ops address."""
    rep, ec = pgs[REP], pgs[EC]
    rep_acting = m.pg_to_up_acting_osds(rep[0])[2]
    # the OSD that goes down is later marked out, as the mon does
    # after mon_osd_down_out_interval
    victim = m.pg_to_up_acting_osds(ec[0])[2][0]
    crush = copy.deepcopy(m.crush)
    root = next(b for b in crush.buckets
                if b is not None and b.type == 10)
    root.item_weights[3] //= 4
    root.weight = sum(root.item_weights)
    e = m.epoch
    return [
        ("osd down", Incremental(epoch=e + 1, new_down_osds=[victim])),
        ("osd out", Incremental(epoch=e + 2,
                                new_weight={victim: CEPH_OSD_OUT})),
        ("pg_temp", Incremental(epoch=e + 3, new_pg_temp={
            rep[1]: [rep_acting[2], rep_acting[1]],
            ec[1]: list(reversed(m.pg_to_up_acting_osds(ec[1])[2]))})),
        ("primary_temp", Incremental(epoch=e + 4, new_primary_temp={
            rep[2]: m.pg_to_up_acting_osds(rep[2])[2][2],
            ec[2]: m.pg_to_up_acting_osds(ec[2])[2][5]})),
        ("pg_upmap_items", Incremental(epoch=e + 5, new_pg_upmap_items={
            rep[0]: [(rep_acting[0], 12 - rep_acting[0] % 12)],
            ec[3]: [(m.pg_to_up_acting_osds(ec[3])[2][0],
                     next(o for o in range(13) if o not in
                          m.pg_to_up_acting_osds(ec[3])[2]))]})),
        ("primary affinity", Incremental(
            epoch=e + 6, new_primary_affinity={0: 0, 5: 0x4000,
                                               9: 0x8000})),
        ("crush map", Incremental(epoch=e + 7, new_crush=crush)),
        ("pg_num and pgp_num", Incremental(epoch=e + 8, new_pools={
            REP: PGPool(pg_num=40, pgp_num=33),
            EC: PGPool(type=POOL_TYPE_ERASURE, size=12, min_size=9,
                       crush_rule=1, pg_num=20, pgp_num=14)})),
    ]


def cold_target(m: OSDMap, pool: int, oid: str, pg_ps=None):
    """(folded PG, target OSD) from a full evaluation of a fresh copy of
    the map at the raw hash, the Objecter's answer before the table."""
    raw = PG(pool, pg_ps) if pg_ps is not None else \
        m.object_locator_to_pg(oid, pool)
    primary = m.pg_to_up_acting_osds(raw)[3]
    return (m.pools[pool].raw_pg_to_pg(raw),
            primary if primary >= 0 and m.is_up(primary) else -1)


@pytest.mark.parametrize("pool,names", [(REP, 64), (EC, 32)],
                         ids=["replicated", "ec"])
def test_targets_match_cold_mapping_across_map_changes(pool, names):
    """Object ops on a few dozen names plus a pgls-style op on every
    pg_ps stay in flight (no replies) while eight new maps arrive; after
    each, every op is aimed exactly where a cold mapping of that epoch's
    map says, and every op whose target moved was resent there."""
    m = make_map()
    o, sent = make_objecter(m)
    ops = [(f"obj-{i}", None) for i in range(names)] + \
        [("", ps) for ps in range(m.pools[pool].pg_num)]
    futs = [o.submit(pool, oid, "read", pg_ps=ps) for oid, ps in ops]
    assert not any(f.done() for f in futs)
    folded = {p: sorted({m.pools[p].raw_pg_to_pg(
        m.object_locator_to_pg(f"obj-{i}", p)) for i in range(64)})
        for p in (REP, EC)}
    steps = [("initial", None)] + map_changes(m, folded)
    moved = 0
    for what, inc in steps:
        if inc is not None:
            before = {t: op.target_osd for t, op in o.in_flight.items()}
            install(o, inc)
            moved += sum(op.target_osd != before.get(t)
                         for t, op in o.in_flight.items())
        fresh = copy.deepcopy(o.osdmap)
        everyone = {op.tid: op for op in
                    list(o.in_flight.values()) + o.homeless}
        assert len(everyone) == len(ops), what
        for op in everyone.values():
            want = cold_target(fresh, pool, op.oid, op.pg_ps)
            assert (op.pg, op.target_osd) == want, \
                f"{what} e{fresh.epoch}: {op.oid or op.pg_ps}"
            if op.target_osd >= 0:
                assert o.in_flight.get(op.tid) is op, what
                assert sent[op.tid] == op.target_osd, what
            else:
                assert op in o.homeless, what
    # the changes did move ops, so the retargeting above was exercised
    assert moved > 0
    # and the pg_num change refolded the ops onto the new PG count
    assert any(op.pg.ps >= m.pools[pool].pg_num
               for op in o.in_flight.values())


def test_folded_pg_key_is_exact():
    """Every raw hash of 128 names per pool maps exactly as its folded PG does,
    in both pools, before and after a pg_num/pgp_num change: the table
    may key on the folded PG."""
    m = make_map()
    steps = map_changes(m, {p: [PG(p, ps) for ps in range(4)]
                            for p in (REP, EC)})
    grown = copy.deepcopy(m)
    grown.apply_incremental(Incremental(
        epoch=m.epoch + 1, new_pools=steps[-1][1].new_pools))
    for omap in (m, grown):
        for pool_id, pool in omap.pools.items():
            by_pg = {}
            for i in range(128):
                raw = omap.object_locator_to_pg(f"name-{i}", pool_id)
                pg = pool.raw_pg_to_pg(raw)
                if pg not in by_pg:
                    by_pg[pg] = omap.pg_to_up_acting_osds(pg)
                assert omap.pg_to_up_acting_osds(raw) == by_pg[pg], raw


@pytest.mark.parametrize("pool", [REP, EC], ids=["replicated", "ec"])
def test_crush_runs_once_per_pg_per_epoch(pool, monkeypatch):
    """N ops over M distinct PGs in one epoch evaluate CRUSH M times and
    read M misses and N - M hits; after a new map the next op to one
    of those PGs misses again."""
    m = make_map()
    o, _ = make_objecter(m)
    calls = []
    real = mapper.do_rule
    monkeypatch.setattr(mapper, "do_rule",
                        lambda *a, **kw: calls.append(a[2]) or
                        real(*a, **kw))
    by_pg: dict = {}
    for i in range(400):
        pg = m.pools[pool].raw_pg_to_pg(
            m.object_locator_to_pg(f"obj-{i}", pool))
        by_pg.setdefault(pg, []).append(f"obj-{i}")
    groups = [names[:4] for names in by_pg.values()
              if len(names) >= 4][:5]
    assert len(groups) == 5
    names = [n for g in groups for n in g]
    futs = [o.submit(pool, n, "write_full", data=b"x") for n in names]
    n, distinct = len(names), len(groups)
    assert len(calls) == distinct
    assert o.perf.get("op_target_misses") == distinct
    assert o.perf.get("op_target_hits") == n - distinct
    assert global_perf().perf_dump()[f"{o.name}.objecter"] == {
        "op_target_hits": n - distinct, "op_target_misses": distinct}
    for tid in list(o.in_flight):
        o._handle_reply(OSDOpReply(tid=tid, result=0))
    assert all(f.done() and f.result == 0 for f in futs)
    # a map that changes nothing placement reads still starts a table
    install(o, Incremental(epoch=m.epoch + 1))
    assert len(calls) == distinct
    o.submit(pool, names[0], "read")
    assert len(calls) == distinct + 1
    assert o.perf.get("op_target_misses") == distinct + 1
    o.submit(pool, names[1], "read")
    assert o.perf.get("op_target_hits") == n - distinct + 1
    o.shutdown()
    assert f"{o.name}.objecter" not in global_perf().perf_dump()
