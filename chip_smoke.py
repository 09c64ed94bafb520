#!/usr/bin/env python
"""Bring the ceph-tpu main path up on a TPU, through its entry points.

    python chip_smoke.py               # one chip: ec, placement, cluster
    python chip_smoke.py --four-chip   # four chips: mesh coder + fabric

Runs in one process; it starts no child that touches JAX, since a
chip belongs to one process.  Each phase prints one JSON line with its
sizes, its compile and steady seconds measured apart, and the device
it ran on.  Any failure exits non-zero.  The last line on success is
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.

There is no CPU fallback: the device phase fails unless JAX reports a
TPU.  Sizes are the published ones; the phase functions take them as
arguments so tests can drive them at a tiny size on the CPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

K, M = 8, 4
ERASURES = (1, 9)                # one data chunk, one parity chunk


def emit(phase: str, **fields) -> None:
    import jax
    dev = jax.devices()[0]
    print(json.dumps({"phase": phase, **fields,
                      "platform": dev.platform,
                      "device_kind": dev.device_kind}), flush=True)


def timed(fn):
    """(result, seconds) of fn() with the device work finished."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def compile_then_steady(fn):
    """First call (compile + one run) and a second call, timed apart."""
    _, compile_s = timed(fn)
    out, steady_s = timed(fn)
    return out, compile_s, steady_s


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_plugin_backend(ec) -> None:
    """The tpu plugin runs its Pallas kernels exactly when JAX's
    backend is the TPU; on the chip anything else hides the device."""
    import jax
    on_tpu = jax.default_backend() == "tpu"
    check(ec._encode_mm.use_pallas == on_tpu,
          f"tpu plugin use_pallas={ec._encode_mm.use_pallas} on "
          f"backend {jax.default_backend()}")


def ec_pool(c, pg_num: int):
    """Client handle and ioctx of a k=8,m=4 plugin=tpu EC pool."""
    r = c.rados(timeout=600.0)
    r.mon_command({"prefix": "osd erasure-code-profile set",
                   "name": "k8m4",
                   "profile": {"plugin": "tpu", "k": str(K), "m": str(M),
                               "crush-failure-domain": "host"}})
    r.pool_create("ec", pg_num=pg_num, pool_type="erasure",
                  erasure_code_profile="k8m4")
    return r, r.open_ioctx("ec")


def kill_osd(c, r, victim: int) -> None:
    """Kill an OSD daemon, mark it down, and wait for the client's map."""
    c.kill_osd(victim)
    r.mon_command({"prefix": "osd down", "ids": [str(victim)]})
    end = time.monotonic() + 60
    while r.objecter.osdmap.is_up(victim):
        check(time.monotonic() < end, f"osd.{victim} never marked down")
        time.sleep(0.05)


# ------------------------------------------------------------------ device

def phase_device(n_chips: int, cache_dir: str):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform "
            f"{devs[0].platform!r}); there is no CPU fallback")
    if len(devs) < n_chips:
        raise SystemExit(
            f"chip_smoke: {n_chips} chips needed, {len(devs)} present")
    warm = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    emit("device", count=len(devs), chips_used=n_chips,
         compile_cache_dir=cache_dir, compile_cache_warm=warm)
    return devs


# ---------------------------------------------------------------------- ec

def phase_ec(rng, stripes: int = 256, chunk: int = 128 * 1024,
             checked: int = 16) -> None:
    """k=8,m=4 encode and decode over `stripes` 1 MiB objects held in
    HBM; sampled stripes against the numpy isa plugin."""
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ec import registry

    tpu = registry.factory("tpu", {"k": str(K), "m": str(M)})
    isa = registry.factory("isa", {"k": str(K), "m": str(M),
                                   "technique": "reed_sol_van"})
    check_plugin_backend(tpu)
    data_np = rng.integers(0, 256, (stripes, K, chunk), dtype=np.uint8)
    data = jax.device_put(data_np)
    parity, enc_c, enc_s = compile_then_steady(
        lambda: tpu.encode_batch(data))
    chunks = jnp.concatenate([data, parity], axis=1)
    decode_index = [i for i in range(K + M) if i not in ERASURES][:K]
    survivors = jnp.take(chunks, jnp.asarray(decode_index), axis=1)
    staged, st_c, st_s = compile_then_steady(
        lambda: tpu.decode_batch(decode_index, list(ERASURES), survivors))

    idx = np.sort(rng.choice(stripes, size=min(checked, stripes),
                             replace=False))
    sel = jnp.asarray(idx)
    got_parity = np.asarray(jnp.take(parity, sel, axis=0))
    got_staged = np.asarray(jnp.take(staged, sel, axis=0))
    mismatches = 0
    for row, s in enumerate(idx):
        want = isa.encode(set(range(K + M)), data_np[s].tobytes())
        want_parity = np.stack([want[K + j] for j in range(M)])
        want_lost = np.stack([want[e] for e in ERASURES])
        mismatches += int(not np.array_equal(got_parity[row],
                                             want_parity))
        mismatches += int(not np.array_equal(got_staged[row], want_lost))
    mb = stripes * K * chunk / 1e6
    emit("ec", k=K, m=M, stripes=stripes, chunk_bytes=chunk,
         data_MB=mb, erasures=list(ERASURES),
         stripes_checked=len(idx), byte_mismatches=mismatches,
         use_pallas=tpu._encode_mm.use_pallas,
         encode_compile_s=enc_c, encode_steady_s=enc_s,
         decode_compile_s=st_c, decode_steady_s=st_s)
    check(mismatches == 0, f"ec: {mismatches} mismatching stripe checks")


# --------------------------------------------------------------- placement

def phase_placement(rng, n_osd: int = 10_000, pg_num: int = 1 << 20,
                    size: int = 3, chunk: int = 1 << 16,
                    sample: int = 256) -> None:
    """Every PG of a straw2 OSDMap through compile_map().map_batch in
    fixed-size dispatches, sampled PGs against the scalar do_rule."""
    from ceph_tpu.crush import mapper
    from ceph_tpu.crush.batch import compile_map
    from ceph_tpu.osd.osdmap import OSDMap
    from ceph_tpu.osd.types import PGPool

    def build():
        m = OSDMap()
        m.build_simple(n_osd, osds_per_host=20, pg_pool=PGPool(
            pg_num=pg_num, pgp_num=pg_num, size=size))
        return m, compile_map(m.crush)

    (m, cc), build_s = timed(build)
    pool = m.pools[0]
    ruleno = m.crush.find_rule(pool.crush_rule, pool.type, pool.size)
    pps = pool.raw_pg_to_pps_batch(np.arange(pg_num, dtype=np.int64), 0)
    weights = np.asarray(m.osd_weight, dtype=np.int64)
    chunk = min(chunk, pg_num)

    def dispatch(lo: int):
        sl = pps[lo:lo + chunk]
        if len(sl) < chunk:          # pad the tail: same executable
            sl = np.concatenate([sl, np.zeros(chunk - len(sl), sl.dtype)])
        return cc.map_batch(sl, weights, ruleno=ruleno, result_max=size)

    def map_all():
        res = np.empty((pg_num, size), dtype=np.int32)
        for lo in range(0, pg_num, chunk):
            hi = min(lo + chunk, pg_num)
            res[lo:hi] = np.asarray(dispatch(lo))[:hi - lo]
        return res

    _, compile_s = timed(lambda: dispatch(0))
    res, steady_s = timed(map_all)

    idx = rng.choice(pg_num, size=min(sample, pg_num), replace=False)
    mismatches = 0
    for ps in idx:
        want = mapper.do_rule(m.crush, ruleno, int(pps[ps]), size,
                              m.osd_weight)
        mismatches += int([int(o) for o in res[ps]][:len(want)]
                          != list(want))
    unmapped = int((res < 0).sum() + (res >= n_osd).sum())
    emit("placement", n_osd=n_osd, pg_num=pg_num, size=size,
         bucket_alg="straw2", pgs_per_dispatch=chunk,
         dispatches=-(-pg_num // chunk), build_s=build_s,
         compile_s=compile_s, steady_s=steady_s,
         pgs_checked=len(idx), placement_mismatches=mismatches,
         out_of_range=unmapped)
    check(mismatches == 0, f"placement: {mismatches} PGs differ from "
                           "the scalar engine")
    check(unmapped == 0, f"placement: {unmapped} slots out of range")


def phase_placement_sweep(xs: int = 60) -> None:
    """Every rule of ceph_tpu.crush.testing.RULES under jewel and
    firefly tunables, batch engine against the scalar one: the check
    that once caught a TPU-only miscompile (EMIT scatter + DCE)."""
    from ceph_tpu.crush import mapper
    from ceph_tpu.crush.batch import compile_map
    from ceph_tpu.crush.testing import RULES, build_hierarchy, make_weight
    from ceph_tpu.crush.types import CrushRule

    def case(rule_name: str, tunables: str) -> int:
        m, root = build_hierarchy(seed=11, tunables=tunables)
        m.rules.append(CrushRule(steps=RULES[rule_name](root)))
        w = make_weight(m.max_devices, seed=1)
        rm = 6 if rule_name == "ec_indep" else 4
        res, cnt = compile_map(m).map_batch(
            np.arange(xs), w, ruleno=0, result_max=rm, return_counts=True)
        res, cnt = np.asarray(res), np.asarray(cnt)
        return sum(list(res[x][:cnt[x]]) != mapper.do_rule(m, 0, x, rm,
                                                           list(w))
                   for x in range(xs))

    cases = [(r, t) for r in sorted(RULES) for t in ("jewel", "firefly")]
    # each case is its own program, and its TPU compile (a minute or
    # two, single-threaded) is most of this phase: compile in parallel
    with ThreadPoolExecutor(max_workers=5) as pool:
        bad, seconds = timed(
            lambda: sum(pool.map(lambda c: case(*c), cases)))
    emit("placement_sweep", cases=len(cases), inputs=len(cases) * xs,
         placement_mismatches=bad, seconds=seconds)
    check(bad == 0, f"placement sweep: {bad} inputs differ from the "
                    "scalar engine")


# ----------------------------------------------------------------- cluster

def phase_cluster(rng, n_osd: int = 13, small: tuple = (64, 1 << 20),
                  large: tuple = (8, 4 << 20)) -> None:
    """An in-process cluster with a k=8,m=4 `tpu` EC pool: write every
    object, read it back, kill one OSD of an acting set, read again."""
    from ceph_tpu.testing import MiniCluster

    objs = {}
    for n, nbytes in (small, large):
        for i in range(n):
            objs[f"obj-{nbytes}-{i}"] = rng.integers(
                0, 256, nbytes, dtype=np.uint8).tobytes()
    total = sum(len(v) for v in objs.values())
    c = MiniCluster(n_osd=n_osd)
    try:
        c.wait_all_up()
        r, io = ec_pool(c, pg_num=16)

        def read_all() -> int:
            return sum(io.read(oid) != data for oid, data in objs.items())

        _, write_s = timed(lambda: [io.write_full(oid, data)
                                    for oid, data in objs.items()])
        bad, read_s = timed(read_all)

        omap = r.objecter.osdmap
        pool_id = r.pool_lookup("ec")
        acting = {oid: omap.pg_to_up_acting_osds(
            omap.object_locator_to_pg(oid, pool_id))[2:] for oid in objs}
        act, primary = acting[next(iter(objs))]
        victim = next(o for o in act if o >= 0 and o != primary)
        degraded = sum(victim in a for a, _ in acting.values())
        kill_osd(c, r, victim)
        bad_degraded, degraded_read_s = timed(read_all)

        plugins = [ec for d in c.osds.values() for ec in d._ecs.values()
                   if ec.get_profile().get("plugin") == "tpu"]
        check(bool(plugins), "no OSD built a tpu plugin")
        for ec in plugins:
            check_plugin_backend(ec)
        decoders = sum(len(ec._decode_mm) for ec in plugins)
    finally:
        c.shutdown()
    emit("cluster", n_osd=n_osd, k=K, m=M, objects=len(objs),
         object_sizes=sorted({len(v) for v in objs.values()}),
         bytes=total, write_ops=len(objs), read_ops=2 * len(objs),
         write_s=write_s, read_s=read_s,
         killed_osd=victim, objects_on_killed_osd=degraded,
         degraded_read_s=degraded_read_s, decoders_built=decoders,
         read_mismatches=bad, degraded_read_mismatches=bad_degraded,
         osd_tpu_plugins=len(plugins))
    check(bad == 0 and bad_degraded == 0,
          f"cluster: {bad} reads, {bad_degraded} degraded reads differ")
    check(degraded > 0 and decoders > 0,
          "cluster: the degraded read decoded nothing")


# -------------------------------------------------------------- four chips

def phase_mesh(rng, n_devices: int = 4, stripes: int = 256,
               chunk: int = 128 * 1024) -> None:
    """MeshECCoder over a (stripe, shard) mesh of n_devices against the
    single-device tpu plugin on device 0."""
    import jax
    from ceph_tpu.dist import MeshECCoder, make_mesh
    from ceph_tpu.ec import registry

    mesh = make_mesh(n_devices, k=K)
    coder = MeshECCoder(K, M, mesh)
    data_np = rng.integers(0, 256, (stripes, K, chunk), dtype=np.uint8)
    data = coder.shard_data(data_np)
    parity, enc_c, enc_s = compile_then_steady(lambda: coder.encode(data))
    spans = len(parity.sharding.device_set)
    decode_index = [i for i in range(K + M) if i not in ERASURES][:K]
    parity_np = np.asarray(parity)
    surv_np = np.ascontiguousarray(np.concatenate(
        [data_np, parity_np], axis=1)[:, decode_index, :])
    survivors = coder.shard_data(surv_np)
    rec, dec_c, dec_s = compile_then_steady(
        lambda: coder.decode(decode_index, list(ERASURES), survivors))

    tpu = registry.factory("tpu", {"k": str(K), "m": str(M)})
    check_plugin_backend(tpu)
    # jax.device_put puts on the default device, device 0
    want_parity = np.asarray(tpu.encode_batch(jax.device_put(data_np)))
    want_rec = np.asarray(tpu.decode_batch(
        decode_index, list(ERASURES), jax.device_put(surv_np)))
    enc_equal = bool(np.array_equal(parity_np, want_parity))
    dec_equal = bool(np.array_equal(np.asarray(rec), want_rec))
    emit("mesh", devices=n_devices, mesh_shape=dict(mesh.shape),
         stripes=stripes, chunk_bytes=chunk,
         data_MB=stripes * K * chunk / 1e6,
         output_devices=spans, encode_equal=enc_equal,
         decode_equal=dec_equal,
         encode_compile_s=enc_c, encode_steady_s=enc_s,
         decode_compile_s=dec_c, decode_steady_s=dec_s)
    check(spans == n_devices,
          f"mesh parity spans {spans} devices, not {n_devices}")
    check(enc_equal and dec_equal,
          "mesh coder differs from the single-device plugin")


def phase_fabric_cluster(rng, n_devices: int, n_osd: int = 16,
                         nbytes: int = 1 << 20) -> None:
    """EC write, read and degraded read whose chunk fan-out rides the
    ICIFabric mesh (modelled on __graft_entry__'s fabric dry run)."""
    from ceph_tpu.dist import ICIFabric
    from ceph_tpu.testing import MiniCluster

    fab = ICIFabric(n_devices)
    payload = rng.integers(0, 256, nbytes, dtype=np.uint8).tobytes()
    c = MiniCluster(n_osd=n_osd, fabric=fab)
    try:
        c.wait_all_up()
        r, io = ec_pool(c, pg_num=8)
        _, write_s = timed(lambda: io.write_full("mesh_e2e", payload))
        read_ok = io.read("mesh_e2e") == payload
        omap = r.objecter.osdmap
        _, _, acting, primary = omap.pg_to_up_acting_osds(
            omap.object_locator_to_pg("mesh_e2e", r.pool_lookup("ec")))
        victim = next(o for o in reversed(acting)
                      if o >= 0 and o != primary)
        kill_osd(c, r, victim)
        degraded_ok = io.read("mesh_e2e") == payload
    finally:
        c.shutdown()
    emit("fabric_cluster", devices=n_devices, n_osd=n_osd, k=K, m=M,
         bytes=nbytes, write_s=write_s, staged=fab.stats["staged"],
         fetched=fab.stats["fetched"], killed_osd=victim,
         read_ok=read_ok, degraded_read_ok=degraded_ok)
    check(fab.stats["staged"] >= 1, "EC write did not ride the fabric")
    check(read_ok and degraded_ok, "fabric cluster read mismatch")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip mesh and fabric path")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated byte")
    args = ap.parse_args(argv)

    from ceph_tpu.common.compile_cache import use_compile_cache
    devs = phase_device(4 if args.four_chip else 1, use_compile_cache())
    rng = np.random.default_rng(args.seed)
    if args.four_chip:
        phase_mesh(rng)
        phase_fabric_cluster(rng, n_devices=4)
    else:
        phase_ec(rng)
        phase_placement(rng)
        phase_placement_sweep()
        phase_cluster(rng)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
