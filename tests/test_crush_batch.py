"""Batch (vmapped) CRUSH mapper vs the scalar oracle.

The scalar engine is validated bit-exact against the reference C core
(tests/test_crush_scalar.py); here the JAX batch engine must reproduce
the scalar engine exactly — including indep NONE holes, firstn skips,
reweight rejections, collisions and chooseleaf recursion."""
import json
import zlib
import os

import numpy as np
import pytest

from ceph_tpu.crush import mapper
from ceph_tpu.crush.batch import BatchUnsupported, compile_map
from ceph_tpu.crush.testing import (RULES, build_hierarchy,
                                    make_weight, map_from_spec)
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_STRAW2, CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP, CRUSH_RULE_CHOOSE_FIRSTN, CRUSH_RULE_EMIT,
    CRUSH_RULE_TAKE, ChooseArg,
    CrushBucket, CrushMap, CrushRule, CrushRuleStep,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures",
                        "crush_vectors.json")


def compare(m, ruleno, result_max, weight, xs):
    cc = compile_map(m)
    res, cnt = cc.map_batch(xs, weight, ruleno=ruleno,
                            result_max=result_max, return_counts=True)
    res = np.asarray(res)
    cnt = np.asarray(cnt)
    for i, x in enumerate(xs):
        want = mapper.do_rule(m, ruleno, int(x), result_max, list(weight))
        got = list(res[i][:cnt[i]])
        assert got == want, (
            f"x={x}: batch {got} != scalar {want} (row {res[i]})")


@pytest.mark.parametrize("rule_name", [
    # two_level is the jit-compile-heaviest shape; it stays in the
    # full suite and the TPU parity sweep but out of the tier-1
    # budget (like the other seed-red heavyweights marked below)
    pytest.param(n, marks=pytest.mark.slow)
    if n == "two_level_firstn" else n
    for n in sorted(RULES)])
@pytest.mark.parametrize("tunables", ["jewel", "firefly"])
def test_batch_matches_scalar(rule_name, tunables):
    # deterministic per-rule seed (hash() varies with PYTHONHASHSEED)
    seed = zlib.crc32(rule_name.encode()) % 1000
    m, root = build_hierarchy(seed=seed, tunables=tunables)
    m.rules.append(CrushRule(steps=RULES[rule_name](root)))
    result_max = 6 if rule_name == "ec_indep" else 4
    weight = make_weight(m.max_devices, seed=1)
    compare(m, 0, result_max, weight, list(range(150)))


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_batch_local_retries():
    # choose_local_tries > 0 exercises the in-bucket collide retry
    m, root = build_hierarchy(seed=7)
    m.choose_local_tries = 2
    m.rules.append(CrushRule(steps=RULES["replicated_firstn"](root)))
    weight = make_weight(m.max_devices, seed=2)
    compare(m, 0, 4, weight, list(range(100)))


def test_batch_all_in_weights():
    m, root = build_hierarchy(seed=3)
    m.rules.append(CrushRule(steps=RULES["ec_indep"](root)))
    weight = np.full(m.max_devices, 0x10000, dtype=np.int64)
    compare(m, 0, 6, weight, list(range(100)))


def test_batch_small_cluster_collisions():
    # tiny cluster: numrep close to device count forces many collisions
    m, root = build_hierarchy(n_racks=1, hosts_per_rack=2,
                              osds_per_host=2, seed=5)
    m.rules.append(CrushRule(steps=[
        CrushRuleStep(CRUSH_RULE_TAKE, root),
        CrushRuleStep(CRUSH_RULE_CHOOSELEAF_INDEP, 0, 1),
        CrushRuleStep(CRUSH_RULE_EMIT),
    ]))
    weight = np.full(m.max_devices, 0x10000, dtype=np.int64)
    compare(m, 0, 4, weight, list(range(100)))


def test_batch_choose_args_weight_set():
    m, root = build_hierarchy(seed=11)
    m.rules.append(CrushRule(steps=RULES["ec_indep"](root)))
    # per-position weight overrides on the root bucket
    rng = np.random.default_rng(4)
    rb = m.bucket(root)
    ws = [[int(rng.integers(1, 8) * 0x10000) for _ in rb.items]
          for _ in range(3)]
    ca = {root: ChooseArg(weight_set=ws)}
    weight = make_weight(m.max_devices, seed=5)
    cc = compile_map(m, choose_args=ca)
    xs = list(range(100))
    res, cnt = cc.map_batch(xs, weight, ruleno=0, result_max=6,
                            return_counts=True)
    res, cnt = np.asarray(res), np.asarray(cnt)
    for i, x in enumerate(xs):
        want = mapper.do_rule(m, 0, x, 6, list(weight), choose_args=ca)
        assert list(res[i][:cnt[i]]) == want, f"x={x}"


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_batch_rejects_legacy_algs():
    with open(FIXTURES) as f:
        cases = json.load(f)
    saw_reject = False
    for name, case in cases.items():
        m = map_from_spec(case["spec"])
        algs = {b.alg for b in m.buckets if b is not None}
        if algs == {CRUSH_BUCKET_STRAW2} and \
                m.choose_local_fallback_tries == 0:
            cc = compile_map(m)
            res, cnt = cc.map_batch(
                case["xs"], case["weights"], ruleno=0,
                result_max=case["result_max"], return_counts=True)
            res, cnt = np.asarray(res), np.asarray(cnt)
            for i, (x, want) in enumerate(zip(case["xs"],
                                              case["expected"])):
                assert list(res[i][:cnt[i]]) == want, f"{name} x={x}"
        else:
            with pytest.raises(BatchUnsupported):
                compile_map(m)
            saw_reject = True
    assert saw_reject  # fixture set includes legacy-alg maps


def test_import_does_not_mutate_global_x64():
    import jax.numpy as jnp
    import ceph_tpu.crush.batch  # noqa: F401
    assert jnp.arange(3).dtype == jnp.int32


def test_result_max_required_for_numrep_zero():
    m, root = build_hierarchy(seed=1)
    m.rules.append(CrushRule(steps=RULES["ec_indep"](root)))
    cc = compile_map(m)
    with pytest.raises(BatchUnsupported, match="numrep <= 0"):
        cc.map_batch([1, 2], make_weight(m.max_devices))


def test_bad_ruleno_raises_batch_unsupported():
    m, root = build_hierarchy(seed=1)
    m.rules.append(CrushRule(steps=RULES["ec_indep"](root)))
    cc = compile_map(m)
    with pytest.raises(BatchUnsupported, match="no rule"):
        cc.map_batch([1], make_weight(m.max_devices), ruleno=5,
                     result_max=6)


def test_dangling_bucket_reference_rejected():
    m, root = build_hierarchy(seed=1)
    m.bucket(root).items[0] = -999  # dangling sub-bucket id
    m.rules.append(CrushRule(steps=RULES["ec_indep"](root)))
    with pytest.raises(BatchUnsupported, match="missing bucket"):
        compile_map(m)


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_default_result_max_covers_chained_chooses():
    m, root = build_hierarchy(seed=2)
    m.rules.append(CrushRule(steps=RULES["two_level_firstn"](root)))
    cc = compile_map(m)
    res = np.asarray(cc.map_batch([1, 2, 3], make_weight(m.max_devices)))
    assert res.shape[1] == 4  # 2 racks x 2 hosts


def test_ln16_table_matches_computed():
    """The precomputed 16-bit ln table is bit-identical to the
    arithmetic crush_ln over the whole straw2 domain."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ceph_tpu.crush import batch as B
    with jax.enable_x64(True):
        u = jnp.arange(65536, dtype=jnp.int64)
        want = np.asarray(B.crush_ln_vec(u))
    assert np.array_equal(B._LN16, want)


# -- weight-class straw2 path (the argmax-u shortcut) ----------------------

def build_flat(weights_list, tunables="jewel"):
    """root -> osds directly, exact weights as given."""
    m = CrushMap()
    m.set_tunables_profile(tunables)
    items = list(range(len(weights_list)))
    root = m.add_bucket(CrushBucket(
        id=0, type=1, alg=CRUSH_BUCKET_STRAW2, items=items,
        item_weights=list(weights_list), weight=sum(weights_list)))
    m.max_devices = len(weights_list)
    m.rules.append(CrushRule(steps=[
        CrushRuleStep(CRUSH_RULE_TAKE, root),
        CrushRuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 3, 0),
        CrushRuleStep(CRUSH_RULE_EMIT)]))
    return m


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_class_path_tie_heavy_matches_scalar():
    """Huge equal weights collapse distinct hashes onto equal draws —
    the exact case where picking the max-u item instead of the FIRST
    max-draw item would silently diverge from bucket_straw2_choose's
    strict-> update.  2000 xs against the scalar engine."""
    w = [0xFFFF0000] * 20          # draws span only ~2^16 values
    m = build_flat(w)
    cc = compile_map(m)
    assert cc.use_classes and cc.n_class_max == 1
    weight = np.full(20, 0x10000, dtype=np.int64)
    xs = np.arange(2000, dtype=np.int64)
    res, cnt = cc.map_batch(xs, weight, ruleno=0, result_max=3,
                            return_counts=True)
    res = np.asarray(res)
    for i, x in enumerate(xs):
        want = mapper.do_rule(m, 0, int(x), 3, list(weight))
        assert list(res[i][:cnt[i]]) == want, f"x={x}"


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_class_path_and_direct_path_agree_heterogeneous():
    """Same map compiled both ways must map identically (and match
    the scalar oracle) with several distinct weight classes."""
    rng = np.random.default_rng(11)
    w = [int(c) for c in rng.choice(
        [0x8000, 0x10000, 0x18000, 0x20000, 0x28000], size=24)]
    m = build_flat(w)
    c_on = compile_map(m, class_path=True)
    c_off = compile_map(m, class_path=False)
    assert c_on.use_classes and not c_off.use_classes
    weight = make_weight(24, seed=3)
    xs = np.arange(1500, dtype=np.int64)
    r_on, n_on = c_on.map_batch(xs, weight, 0, 3, return_counts=True)
    r_off, n_off = c_off.map_batch(xs, weight, 0, 3,
                                   return_counts=True)
    assert (np.asarray(r_on) == np.asarray(r_off)).all()
    assert (np.asarray(n_on) == np.asarray(n_off)).all()
    for x in range(0, 1500, 97):
        want = mapper.do_rule(m, 0, x, 3, list(weight))
        got = list(np.asarray(r_on)[x][:np.asarray(n_on)[x]])
        assert got == want, f"x={x}"


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_class_path_auto_disables_past_threshold():
    """More distinct weights than CLASS_PATH_MAX -> auto fallback to
    the direct per-item ln path; forcing class_path=True must still
    be bit-identical."""
    from ceph_tpu.crush.batch import CLASS_PATH_MAX
    n = CLASS_PATH_MAX + 8
    w = [0x10000 + i * 0x100 for i in range(n)]   # all distinct
    m = build_flat(w)
    auto = compile_map(m)
    assert not auto.use_classes
    forced = compile_map(m, class_path=True)
    assert forced.use_classes and forced.n_class_max == n
    weight = np.full(n, 0x10000, dtype=np.int64)
    xs = np.arange(800, dtype=np.int64)
    r_a, n_a = auto.map_batch(xs, weight, 0, 3, return_counts=True)
    r_f, n_f = forced.map_batch(xs, weight, 0, 3, return_counts=True)
    assert (np.asarray(r_a) == np.asarray(r_f)).all()
    assert (np.asarray(n_a) == np.asarray(n_f)).all()


@pytest.mark.slow   # jit-compile-heavy on current jax; full-suite only (tier-1 budget)
def test_class_path_ln_boundary_and_wide_sweep():
    """crush_ln dips at u=65535 (x=u+1 overflows the normalization) —
    the class path orders hashes through a key space that swaps the
    65534/65535 pair.  Sweep enough xs that several draws hit those
    boundary hashes, comparing against the direct per-item-ln path
    (itself fixture-pinned to the C core), plus scalar spot checks.
    Regression for the 1M-PG bench divergence at pps=1420417868."""
    from ceph_tpu.crush.batch import LN16_MONO_BY_SWAP
    assert LN16_MONO_BY_SWAP
    m = build_flat([0x20000] * 16)
    c_on = compile_map(m, class_path=True)
    c_off = compile_map(m, class_path=False)
    weight = np.full(16, 0x10000, dtype=np.int64)
    xs = np.arange(120_000, dtype=np.int64)
    r_on, n_on = c_on.map_batch(xs, weight, 0, 3, return_counts=True)
    r_off, n_off = c_off.map_batch(xs, weight, 0, 3,
                                   return_counts=True)
    r_on, r_off = np.asarray(r_on), np.asarray(r_off)
    bad = np.nonzero((r_on != r_off).any(axis=1))[0]
    assert bad.size == 0, f"diverged at xs {bad[:5]}"
    assert (np.asarray(n_on) == np.asarray(n_off)).all()
    for x in (0, 31337, 65534, 65535, 119_999):
        want = mapper.do_rule(m, 0, x, 3, list(weight))
        assert list(r_on[x][:np.asarray(n_on)[x]]) == want, f"x={x}"


# -- tie-floor bound of the class path (tier-1 guard of the search) --------

def _brute_tie_floor(w):
    """For every key, the first key of equal draw at weight w, taken
    key by key from the ln table in the class path's key order."""
    from ceph_tpu.crush import batch as B
    keys = np.arange(65536)
    q = (B.LN_BIAS - B._LN16_KEYED) // w
    starts = np.r_[True, q[1:] != q[:-1]]
    return np.maximum.accumulate(np.where(starts, keys, 0))


def _bounded_search(w, probes):
    """_straw2's tie-floor search, in numpy, for every key as kmax."""
    from ceph_tpu.crush import batch as B
    kmax = np.arange(65536, dtype=np.int64)
    absln = B.LN_BIAS - B._LN16_KEYED
    x_thr = B.LN_BIAS - (absln // w + 1) * w + 1
    lo = np.maximum(kmax - ((1 << probes) - 1), 0)
    hi = kmax.copy()
    for _ in range(probes):
        mid = (lo + hi) >> 1
        ok = B._LN16_KEYED[mid] >= x_thr
        hi = np.where(ok, mid, hi)
        lo = np.where(ok, lo, mid + 1)
    return hi


@pytest.mark.parametrize("w,span,probes", [
    (0x10000, 1, 1),       # osdmaptool --createsimple OSDs
    (0x140000, 1, 1),      # its 20-OSD hosts
    (0x7d00000, 1, 2),
    (0xFFFF0000, 11, 4),
    (0xFFFFFFFF, 11, 4),   # the largest u32 weight
])
def test_tie_bound_covers_brute_force_floor(w, span, probes):
    """The bound compile_map takes its probe count from is never below
    the widest tie the ln table makes at w, and the bounded search
    finds the brute-force floor for every key."""
    from ceph_tpu.crush import batch as B
    floor = _brute_tie_floor(w)
    widest = int((np.arange(65536) - floor).max())
    assert widest == span
    assert widest <= B.tie_bound(w)
    assert B.tie_bound(w).bit_length() == probes
    assert np.array_equal(_bounded_search(w, probes), floor)
    # the bound is monotone in w, so no u32 weight needs more probes
    assert (np.diff(B._TIE_GAP) >= 0).all()
    assert B.tie_bound(0xFFFFFFFF).bit_length() == 4
    assert B.tie_bound(1 << 62).bit_length() == 16


def _tie_decided_xs(w, n_items, want):
    """xs whose first draws (r = 0..2) in a flat bucket of n_items
    equal weights w are won by an item other than the max-key one:
    the draws the tie floor decides."""
    from ceph_tpu.crush import batch as B
    from ceph_tpu.crush.hashes import hash32_3
    xs = np.arange(60_000, dtype=np.int64)
    hit = np.zeros(len(xs), dtype=bool)
    ids = np.tile(np.arange(n_items), len(xs))
    for r in range(3):
        u = hash32_3(np.repeat(xs, n_items), ids,
                     np.full(len(ids), r)).astype(np.int64)
        u = (u & 0xFFFF).reshape(len(xs), n_items)
        draws = -((B.LN_BIAS - B._LN16[u]) // w)
        key = np.where(u == 65534, 65535, np.where(u == 65535, 65534, u))
        hit |= draws.argmax(1) != key.argmax(1)
    return xs[hit][:want]


def _parity(m, weight, xs, result_max, probes):
    c_on = compile_map(m)
    c_off = compile_map(m, class_path=False)
    assert c_on.use_classes and c_on.tie_probes == probes
    assert not c_off.use_classes and c_off.tie_probes == 0
    r_on, n_on = c_on.map_batch(xs, weight, 0, result_max,
                                return_counts=True)
    r_off, n_off = c_off.map_batch(xs, weight, 0, result_max,
                                   return_counts=True)
    r_on, n_on = np.asarray(r_on), np.asarray(n_on)
    assert (r_on == np.asarray(r_off)).all()
    assert (n_on == np.asarray(n_off)).all()
    for i in range(0, len(xs), 17):
        want = mapper.do_rule(m, 0, int(xs[i]), result_max, list(weight))
        assert list(r_on[i][:n_on[i]]) == want, f"x={xs[i]}"


@pytest.mark.parametrize("shape", ["simple", "tie_heavy"])
def test_bounded_tie_floor_matches_direct_path(shape):
    """The class path with its weight-bounded tie-floor search maps
    exactly as the direct path and the scalar engine do, on an
    osdmaptool-style map with an OSD out and one at crush weight 0,
    and on the tie-heavy flat map."""
    if shape == "simple":
        from ceph_tpu.osd.osdmap import OSDMap
        om = OSDMap()
        om.build_simple(200, osds_per_host=20)
        om.osd_weight[7] = 0
        host = om.crush.buckets[4]
        host.item_weights[2] = 0         # osd 82
        m = om.crush
        weight = np.asarray(om.osd_weight, dtype=np.int64)
        xs = np.random.default_rng(24).integers(
            0, 1 << 32, size=400, dtype=np.int64)
        _parity(m, weight, xs, 3, probes=1)
    else:
        m = build_flat([0xFFFF0000] * 20)
        weight = np.full(20, 0x10000, dtype=np.int64)
        xs = _tie_decided_xs(0xFFFF0000, 20, 100)
        assert len(xs) == 100
        xs = np.concatenate([xs, np.arange(300, dtype=np.int64)])
        _parity(m, weight, xs, 3, probes=4)


# -- static draw widths (each straw2 draw reads only the lanes it can use) --

def _build_uneven(rack_hosts=((3, 20), (7, 4, 5)), seed=0):
    """root(type 3) -> racks(2) -> hosts(1) -> osds(0), hosts of the given
    sizes, OSD crush weights from two classes."""
    rng = np.random.default_rng(seed)
    m = CrushMap()
    m.set_tunables_profile("jewel")
    osd = 0
    racks = []
    for sizes in rack_hosts:
        hosts = []
        for n in sizes:
            items = list(range(osd, osd + n))
            osd += n
            ws = [int(rng.choice([0x10000, 0x18000])) for _ in items]
            hosts.append(m.add_bucket(CrushBucket(
                id=0, type=1, alg=CRUSH_BUCKET_STRAW2, items=items,
                item_weights=ws, weight=sum(ws))))
        hw = [m.bucket(h).weight for h in hosts]
        racks.append(m.add_bucket(CrushBucket(
            id=0, type=2, alg=CRUSH_BUCKET_STRAW2, items=hosts,
            item_weights=hw, weight=sum(hw))))
    rw = [m.bucket(r).weight for r in racks]
    root = m.add_bucket(CrushBucket(
        id=0, type=3, alg=CRUSH_BUCKET_STRAW2, items=racks,
        item_weights=rw, weight=sum(rw)))
    m.max_devices = osd
    return m, root, racks


def _simple(n_osd):
    from ceph_tpu.osd.osdmap import OSDMap
    om = OSDMap()
    om.build_simple(n_osd, osds_per_host=20)
    return om.crush


def _uneven_rule(name):
    m, root, racks = _build_uneven()
    steps = {
        "chooseleaf_host": [
            CrushRuleStep(CRUSH_RULE_TAKE, root),
            CrushRuleStep(CRUSH_RULE_CHOOSELEAF_FIRSTN, 0, 1),
            CrushRuleStep(CRUSH_RULE_EMIT)],
        "take_rack": [
            CrushRuleStep(CRUSH_RULE_TAKE, racks[1]),
            CrushRuleStep(CRUSH_RULE_CHOOSELEAF_INDEP, 0, 1),
            CrushRuleStep(CRUSH_RULE_EMIT)],
        "two_step": [
            CrushRuleStep(CRUSH_RULE_TAKE, root),
            CrushRuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 2, 2),
            CrushRuleStep(CRUSH_RULE_CHOOSELEAF_FIRSTN, 2, 1),
            CrushRuleStep(CRUSH_RULE_EMIT)],
        "missing_take": [
            CrushRuleStep(CRUSH_RULE_TAKE, -999),
            CrushRuleStep(CRUSH_RULE_CHOOSELEAF_FIRSTN, 0, 1),
            CrushRuleStep(CRUSH_RULE_EMIT)],
    }[name]
    m.rules.append(CrushRule(steps=steps))
    return m


@pytest.mark.parametrize("case,want", [
    # osdmaptool --createsimple: the root is narrower than its hosts
    ("simple_200", ((10, 20),)),
    ("simple_10000", ((500, 20),)),
    # root -> rack -> host: draws to a host pass root (2) and racks (3)
    ("chooseleaf_host", ((3, 20),)),
    # a take below the root reaches only that rack's hosts (7, 4, 5)
    ("take_rack", ((3, 7),)),
    # choose racks from the root, then hosts from the racks
    ("two_step", ((2, 0), (3, 20))),
    # no bucket to start from: only masked lanes draw, map-wide width
    ("missing_take", ((20, 20),)),
])
def test_draw_widths(case, want):
    """The per-step straw2 widths compile_map derives from the map's
    bucket tree: the widest bucket each descent can draw from."""
    if case.startswith("simple_"):
        m = _simple(int(case.split("_")[1]))
    else:
        m = _uneven_rule(case)
    cc = compile_map(m)
    assert cc.draw_widths == (want,)
    assert cc.static_cfg(0, 6).draw_widths == want
    assert max(w for pair in want for w in pair) <= cc.items.shape[1]


def test_draw_widths_logged_with_lane_fill(monkeypatch):
    import logging
    from ceph_tpu.common import log
    m = _simple(10000)
    lines = []

    class Grab(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())
    lg = logging.getLogger("ceph_tpu.crush")
    h = Grab()
    lg.addHandler(h)
    monkeypatch.setitem(log._levels, "crush", 10)
    try:
        compile_map(m)
    finally:
        lg.removeHandler(h)
    assert ("compile_map: rule 0 draw widths ((500, 20),) lane fill 1.000 "
            "(0.520 at the map-wide width 500)") in lines


@pytest.mark.parametrize("class_path", [True, False])
def test_narrow_draws_match_scalar(class_path):
    """Hosts of 3, 20 and 7 OSDs under two racks, one OSD out and one
    partly reweighted: a chooseleaf firstn segment and a chooseleaf
    indep (EC) segment in one rule, so one program, match the scalar
    engine on both straw2 formulations."""
    m, root, _ = _build_uneven(rack_hosts=((3, 20), (7,)), seed=3)
    m.rules.append(CrushRule(steps=[
        CrushRuleStep(CRUSH_RULE_TAKE, root),
        CrushRuleStep(CRUSH_RULE_CHOOSELEAF_FIRSTN, 2, 1),
        CrushRuleStep(CRUSH_RULE_EMIT),
        CrushRuleStep(CRUSH_RULE_TAKE, root),
        CrushRuleStep(CRUSH_RULE_CHOOSELEAF_INDEP, 3, 1),
        CrushRuleStep(CRUSH_RULE_EMIT)]))
    cc = compile_map(m, class_path=class_path)
    assert cc.use_classes == class_path
    assert cc.draw_widths == (((2, 20), (2, 20)),)
    weight = np.full(m.max_devices, 0x10000, dtype=np.int64)
    weight[4] = 0            # in the 20-OSD host
    weight[25] = 0x8000      # in the 7-OSD host
    xs = np.random.default_rng(26).integers(0, 1 << 32, size=300,
                                            dtype=np.int64)
    res, cnt = cc.map_batch(xs, weight, ruleno=0, result_max=5,
                            return_counts=True)
    res, cnt = np.asarray(res), np.asarray(cnt)
    for i, x in enumerate(xs):
        want = mapper.do_rule(m, 0, int(x), 5, list(weight))
        assert list(res[i][:cnt[i]]) == want, f"x={x}"


def test_static_cfg_keys_on_shape_not_weights():
    """Maps that differ only in crush weights share an executable; a map
    with one host wider than the rest draws its OSDs wider."""
    a, b = _simple(200), _simple(200)
    a.buckets[3].item_weights[5] = 0x18000
    b.buckets[7].item_weights[2] = 0x8000
    ka = compile_map(a).static_cfg(0, 3)
    kb = compile_map(b).static_cfg(0, 3)
    assert ka == kb and hash(ka) == hash(kb)
    wide = _simple(200)
    host = wide.buckets[4]
    host.items += list(range(200, 206))
    host.item_weights += [0x10000] * 6
    wide.max_devices = 206
    cw = compile_map(wide)
    assert cw.draw_widths == (((10, 26),),)
    assert cw.static_cfg(0, 3) != ka
