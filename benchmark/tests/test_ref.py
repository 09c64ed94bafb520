"""The plain references agree with the program where both are sound,
so a disagreement in a run is the program's, not the reference's."""
import numpy as np
import pytest

from benchmark.loop import percentile
from benchmark.ref import crush, gf256


def test_gf_matrix_and_encode_match_isa_plugin():
    from ceph_tpu.ec import gf, registry
    assert (gf256.rs_matrix(8, 4) == gf.isa_rs_matrix(8, 4)[8:]).all()
    isa = registry.factory("isa", {"k": "8", "m": "4",
                                   "technique": "reed_sol_van"})
    rng = np.random.default_rng(7)
    for size in (4096, 3 * 8 * 4096):
        payload = rng.bytes(size)
        got = gf256.shard_streams(payload, 8, 4, 4096)
        padded = payload + bytes(-size % (8 * 4096))
        for s in range(len(padded) // (8 * 4096)):
            stripe = padded[s * 8 * 4096:(s + 1) * 8 * 4096]
            want = isa.encode(set(range(12)), stripe)
            for i in range(12):
                assert bytes(np.asarray(want[i])) == \
                    got[i][s * 4096:(s + 1) * 4096]


@pytest.mark.parametrize("pg_num", [1000, 1024])
def test_crush_matches_scalar_pipeline(pg_num):
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PG, PGPool
    m = OSDMap()
    m.build_simple(200, osds_per_host=20,
                   pg_pool=PGPool(pg_num=pg_num, pgp_num=pg_num, size=3))
    for o in (3, 77, 150):
        m.osd_weight[o] = 0
    m.osd_weight[9] = 0x8000
    ref = crush.SimpleMap(200, 20, pg_num, 3)
    for ps in range(0, pg_num, 7):
        want = m.pg_to_up_acting_osds(PG(0, ps))[2]
        assert ref.map_pg(ps, m.osd_weight) == want, ps


def test_percentile_is_nearest_rank():
    vals = list(range(1, 201))
    assert percentile(vals, 0.95) == 190
    assert percentile(vals, 0.5) == 100
    assert percentile([5.0], 0.95) == 5.0
