#!/usr/bin/env python
"""Placement throughput at BASELINE scale: 1M PGs x 10k OSDs straw2.

The `osdmaptool --createsimple 10000 --test-map-pgs` scenario
(ref: src/tools/osdmaptool.cc:31,38; the threaded bulk path it models
is ParallelPGMapper, src/osd/OSDMapMapping.h:18) run through the
batched vmapped CRUSH mapper on device, with:

* identity verification against the scalar oracle on a PG sample
  (the scalar engine is fixture-validated against the reference C);
* a `calc_pg_upmaps` balancer pass at the same scale on the batched
  mapping (ref: src/osd/OSDMap.cc:4360).

Prints one JSON line and (with --write) records PLACEMENT_BENCH.json
at the repo root.  Scale is parameterized so the test tier can run a
reduced configuration (tests/test_placement_scale.py).
"""
import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def build_map(n_osd: int, pg_num: int, osds_per_host: int = 20):
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PGPool
    m = OSDMap()
    m.build_simple(n_osd, osds_per_host=osds_per_host,
                   pg_pool=PGPool(pg_num=pg_num, pgp_num=pg_num, size=3))
    return m


def run(n_osd: int, pg_num: int, sample: int = 256,
        balancer_iters: int = 10, chunk: int = 1 << 16) -> dict:
    import jax

    from ceph_tpu.crush import mapper as scalar
    from ceph_tpu.crush.batch import compile_map
    from ceph_tpu.osd.mapping import OSDMapMapping

    m = build_map(n_osd, pg_num)
    pool = m.pools[0]
    ruleno = m.crush.find_rule(pool.crush_rule, pool.type, pool.size)
    pss = np.arange(pg_num, dtype=np.int64)
    pps = pool.raw_pg_to_pps_batch(pss, 0)
    weights = np.asarray(m.osd_weight, dtype=np.int64)

    cc = compile_map(m.crush)

    # fixed-size dispatches: one compiled executable reused across the
    # whole PG space, bounded device memory (the 1M-PG batch in one
    # dispatch overruns a v5e-1's HBM working set)
    chunk = min(chunk, pg_num)

    def map_all():
        out = np.empty((pg_num, pool.size), dtype=np.int32)
        for lo in range(0, pg_num, chunk):
            hi = min(lo + chunk, pg_num)
            sl = pps[lo:hi]
            if len(sl) < chunk:       # pad the tail: same executable
                sl = np.concatenate(
                    [sl, np.zeros(chunk - len(sl), dtype=sl.dtype)])
            r = np.asarray(cc.map_batch(sl, weights, ruleno=ruleno,
                                        result_max=pool.size))
            out[lo:hi] = r[:hi - lo]
        return out

    res = map_all()                   # warm: compile + first pass
    t0 = time.perf_counter()
    res = map_all()
    # map_all converts per-chunk via np.asarray (a sync), but the
    # explicit barrier keeps the measurement honest if that ever
    # changes (cephck jax-timing)
    jax.block_until_ready(res)
    dt = time.perf_counter() - t0
    mappings_per_s = pg_num / dt

    # identity vs the scalar oracle on a sample
    rng = np.random.default_rng(0)
    idx = rng.choice(pg_num, size=min(sample, pg_num), replace=False)
    for ps in idx:
        want = scalar.do_rule(m.crush, ruleno, int(pps[ps]), pool.size,
                              m.osd_weight)
        got = [int(o) for o in res[ps]][:len(want)]
        if got != list(want):
            raise AssertionError(
                f"batch/scalar mismatch at ps={ps}: {got} != {want}")

    # distribution sanity: every up OSD carries PGs
    flat = res[res >= 0]
    counts = np.bincount(flat, minlength=n_osd)
    stats = {"min": int(counts.min()), "max": int(counts.max()),
             "mean": float(counts.mean()), "std": float(counts.std())}

    # full OSDMapMapping table build (includes post-processing) + the
    # balancer pass on the batched mapping
    mapping = OSDMapMapping()
    t0 = time.perf_counter()
    mapping.update(m)
    t_tables = time.perf_counter() - t0

    from ceph_tpu.osd.balancer import calc_pg_upmaps
    from ceph_tpu.osd.osdmap import Incremental
    inc = Incremental(epoch=m.epoch + 1)
    t0 = time.perf_counter()
    nch = calc_pg_upmaps(m, 0.01, balancer_iters, None, inc,
                         mapping=mapping)
    t_upmap = time.perf_counter() - t0

    out = {
        "metric": "crush_mappings_per_s",
        "value": round(mappings_per_s, 1),
        "unit": "mappings/s",
        "detail": {
            "n_osd": n_osd, "pg_num": pg_num, "size": pool.size,
            "bucket_alg": "straw2",
            "map_batch_seconds": round(dt, 4),
            "full_table_update_seconds": round(t_tables, 4),
            "scalar_identity_sample": int(len(idx)),
            "pgs_per_osd": stats,
            "calc_pg_upmaps": {"iterations": balancer_iters,
                               "changes": nch,
                               "seconds": round(t_upmap, 3)},
            "backend": _backend(),
        },
    }
    if pg_num == BASELINE_PG_NUM and n_osd == BASELINE_N_OSD:
        out["detail"]["baseline_mappings_per_s"] = BASELINE_MAPPINGS_PER_S
        out["detail"]["baseline_engine"] = BASELINE_ENGINE
        out["vs_baseline"] = round(
            mappings_per_s / BASELINE_MAPPINGS_PER_S, 3)
    return out


#: reference C core throughput on this host at the canonical scale,
#: measured by scripts/placement_baseline.py (oracle_map_bulk: one
#: C-side loop over all 1M PGs, -O2, single thread) — re-run that
#: script to refresh after a toolchain change
BASELINE_PG_NUM = 1 << 20
BASELINE_N_OSD = 10_000
BASELINE_MAPPINGS_PER_S = 7468.8
BASELINE_ENGINE = "reference crush C core, 1 thread (-O2)"


def _backend() -> str:
    import jax
    return jax.default_backend()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-osd", type=int, default=10_000)
    ap.add_argument("--pg-num", type=int, default=1 << 20)
    ap.add_argument("--sample", type=int, default=256)
    ap.add_argument("--write", action="store_true",
                    help="record PLACEMENT_BENCH.json at the repo root")
    a = ap.parse_args()
    from ceph_tpu.common.compile_cache import use_compile_cache
    use_compile_cache()
    out = run(a.n_osd, a.pg_num, a.sample)
    line = json.dumps(out)
    print(line)
    if a.write:
        root = pathlib.Path(__file__).resolve().parent.parent
        with open(root / "PLACEMENT_BENCH.json", "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
