"""Placement at a reduced scale of osdmaptool's bulk mapping: the
batched OSDMapMapping tables agree with the scalar engine on a sample,
the distribution is sane, and a balancer pass runs over the batched
mapping (ref: src/tools/osdmaptool.cc --test-map-pgs;
src/osd/OSDMap.cc:4360 calc_pg_upmaps)."""
import numpy as np
import pytest

N_OSD, PG_NUM, SAMPLE = 500, 1 << 14, 64


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_placement_reduced_scale():
    from ceph_tpu.crush import mapper
    from ceph_tpu.osd.balancer import calc_pg_upmaps
    from ceph_tpu.osd.mapping import OSDMapMapping
    from ceph_tpu.osd.osdmap import Incremental, OSDMap
    from ceph_tpu.osd.types import PGPool

    m = OSDMap()
    m.build_simple(N_OSD, osds_per_host=20, pg_pool=PGPool(
        pg_num=PG_NUM, pgp_num=PG_NUM, size=3))
    mapping = OSDMapMapping()
    mapping.update(m)
    pool, up = m.pools[0], mapping.pools[0].up
    ruleno = m.crush.find_rule(pool.crush_rule, pool.type, pool.size)
    pps = pool.raw_pg_to_pps_batch(np.arange(PG_NUM, dtype=np.int64), 0)
    # batched tables identical to the scalar engine on a sample
    rng = np.random.default_rng(0)
    sample = rng.choice(PG_NUM, size=SAMPLE, replace=False)
    for ps in sample:
        want = mapper.do_rule(m.crush, ruleno, int(pps[ps]), pool.size,
                              m.osd_weight)
        assert [int(o) for o in up[ps]][:len(want)] == list(want), ps
    # every OSD carries PGs and the spread is plausible for straw2
    counts = np.bincount(up[up >= 0], minlength=N_OSD)
    assert counts.min() > 0
    assert counts.max() < 6 * counts.mean()
    inc = Incremental(epoch=m.epoch + 1)
    assert calc_pg_upmaps(m, 0.01, 3, None, inc, mapping=mapping) >= 0
