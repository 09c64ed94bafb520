"""Full-map CRUSH remaps at osdmaptool --createsimple scale.

Each pass of the window is one map change and the full recomputation
of placement that follows it: one seeded OSD is marked out and the one
marked out before it back in (an Incremental, so a new epoch), then
OSDMapMapping.update recomputes every PG's up and acting sets, in
64Ki-PG device dispatches plus the host epilogue.

`check` holds every pass's tables to benchmark/ref/crush, the scalar
pipeline written from upstream mapper.c, on PGs sampled from the seed
and on PGs the newly out OSD held, and checks that no PG maps to an
OSD that is out.

Configuration keys: osds, osds_per_host, pg_num, size.  Mix keys:
sample_pgs, sample_moved_pgs, check_passes.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.ref.crush import NONE, SimpleMap

IN = 0x10000


class State:
    pass


def setup(cfg: dict, mix: dict, seed: int, trace: bool = False) -> State:
    from ceph_tpu.osd.mapping import OSDMapMapping
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PGPool

    s = State()
    s.cfg, s.mix, s.seed = cfg, mix, seed
    n, pg_num = int(cfg["osds"]), int(cfg["pg_num"])
    s.osdmap = OSDMap()
    s.osdmap.build_simple(n, osds_per_host=int(cfg["osds_per_host"]),
                          pg_pool=PGPool(pg_num=pg_num, pgp_num=pg_num,
                                         size=int(cfg["size"])))
    s.order = np.random.default_rng([seed, 0]).permutation(n)
    s.mapping = OSDMapMapping()
    # warm-up: the whole map once, which compiles (or loads) the
    # 64Ki-PG dispatch and the padded tail's
    s.mapping.update(s.osdmap)
    s.passes = []
    return s


def window(s, seconds: float, probe) -> dict:
    from ceph_tpu.osd.osdmap import Incremental

    t0 = time.monotonic()
    deadline = t0 + seconds
    prev = None
    p = 0
    prof_passes = 0
    while time.monotonic() < deadline or p == 0:
        if p == 1:
            probe.start()
        out = int(s.order[p % len(s.order)])
        weights = {out: 0}
        if prev is not None:
            weights[prev] = IN
        with probe.annotate("incremental"):
            s.osdmap.apply_incremental(Incremental(
                epoch=s.osdmap.epoch + 1, new_weight=weights))
        with probe.annotate("update"):
            s.mapping.update(s.osdmap)
        pm = s.mapping.pools[0]
        s.passes.append({"epoch": s.osdmap.epoch,
                         "table_epoch": s.mapping.epoch,
                         "out": out,
                         "up": pm.up, "up_primary": pm.up_primary,
                         "acting": pm.acting,
                         "acting_primary": pm.acting_primary,
                         "up_len": pm.up_len, "acting_len": pm.acting_len})
        if p in (1, 2):
            prof_passes += 1
            if p == 2:
                probe.stop()
        prev = out
        p += 1
    t1 = time.monotonic()
    probe.stop()
    moved = [int(np.count_nonzero((a["up"] != b["up"]).any(axis=1)))
             for a, b in zip(s.passes, s.passes[1:])]
    out = {"metrics": {"remap_s": (t1 - t0) / p},
           "attempted": p, "failed": 0,
           "notes": {"passes": p, "window_s": t1 - t0,
                     "pgs_moved_per_pass": moved}}
    if probe.t0 is not None:
        out["profiled"] = {"passes": prof_passes}
    return out


def check(s) -> dict:
    cfg, mix = s.cfg, s.mix
    n = int(cfg["osds"])
    ref = SimpleMap(n, int(cfg["osds_per_host"]), int(cfg["pg_num"]),
                    int(cfg["size"]))
    wrong = 0
    on_out = 0
    stale = 0
    for p, ps_rec in enumerate(s.passes):
        stale += int(ps_rec["table_epoch"] != ps_rec["epoch"])
    # every pass of a sound run (some ten); a broken update that runs
    # thousands of passes is held to a seeded sample of them
    picked = range(len(s.passes))
    if len(s.passes) > int(mix["check_passes"]):
        picked = sorted(np.random.default_rng([s.seed, 2]).choice(
            len(s.passes), size=int(mix["check_passes"]), replace=False))
    for p in picked:
        ps_rec = s.passes[p]
        reweight = np.full(n, IN, dtype=np.int64)
        reweight[ps_rec["out"]] = 0
        on_out += int(np.count_nonzero(ps_rec["acting"] == ps_rec["out"]))
        rng = np.random.default_rng([s.seed, 1, p])
        pgs = set(rng.choice(int(cfg["pg_num"]),
                             size=int(mix["sample_pgs"]),
                             replace=False).tolist())
        if p > 0:
            # PGs that had to move: the previous pass's rows holding
            # the OSD this pass marked out
            held = np.flatnonzero(
                (s.passes[p - 1]["up"] == ps_rec["out"]).any(axis=1))
            if len(held):
                pgs.update(rng.choice(held, size=min(
                    len(held), int(mix["sample_moved_pgs"])),
                    replace=False).tolist())
        for ps in sorted(pgs):
            want = ref.map_pg(int(ps), reweight)
            wrong += int(_row(ps_rec, "up", ps) != want
                         or _row(ps_rec, "acting", ps) != want
                         or int(ps_rec["up_primary"][ps])
                         != (want[0] if want else -1)
                         or int(ps_rec["acting_primary"][ps])
                         != (want[0] if want else -1))
    return {
        "placement_rows_wrong": {"value": wrong, "limit": 0},
        "pgs_on_out_osd": {"value": on_out, "limit": 0},
        "tables_of_stale_epoch": {"value": stale, "limit": 0},
    }


def _row(rec: dict, table: str, ps: int) -> list:
    row = rec[table][ps][:rec[table + "_len"][ps]]
    return [int(o) for o in row if o != NONE]


def teardown(s) -> None:
    s.passes = []
