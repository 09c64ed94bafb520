"""An erasure-coded pool on an in-process MiniCluster, driven by
`rados bench`-style closed-loop traffic through librados.

The window's entry is IoCtx.aio_write_full / IoCtx.aio_read: objecter
-> OSD primary -> ECBackend -> ecutil -> the pool's plugin -> device ->
MemStore.  `check` holds every answer to the plain references: each
read's bytes against the payload written, a seeded sample of objects
read back after the window, and every live OSD's stored shard of every
object against benchmark/ref/gf256's encode of its last acknowledged
payload.

Configuration keys: osds, osds_per_host, k, m, plugin, pg_num,
stripe_unit, failure_domain.  Mix keys: op ("write_full" or "read"),
object_bytes, in_flight, objects, payloads, kill_osds, op_timeout_s,
readback_sample.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.loop import closed_loop, percentile
from benchmark.ref import gf256


class State:
    pass


def _tracers(s) -> list:
    return [s.rados.objecter.tracer] + [d.tracer
                                        for d in s.cluster.osds.values()]


def setup(cfg: dict, mix: dict, seed: int, trace: bool = False) -> State:
    from ceph_tpu.common.options import global_config
    from ceph_tpu.testing import MiniCluster

    if cfg["stripe_unit"] != 4096:
        raise ValueError("the OSD's EC stripe unit is fixed at 4096")
    global_config().set("blkin_trace_all", bool(trace))
    s = State()
    s.cfg, s.mix, s.seed = cfg, mix, seed
    s.k, s.m = int(cfg["k"]), int(cfg["m"])
    rng = np.random.default_rng([seed, 0])
    size = int(mix["object_bytes"])
    s.size = size
    s.payloads = [rng.bytes(size) for _ in range(int(mix["payloads"]))]
    s.names = [f"bench-{j}" for j in range(int(mix["objects"]))]
    s.last: dict[str, int] = {}        # name -> payload index stored
    s.dead: list[int] = []
    s.wrong_reads = 0
    s.failed_ops = 0
    s.cluster = MiniCluster(n_osd=int(cfg["osds"]),
                            osds_per_host=int(cfg["osds_per_host"]))
    s.cluster.wait_all_up(timeout=120)
    s.rados = s.cluster.rados(timeout=float(mix["op_timeout_s"]))
    s.rados.mon_command({
        "prefix": "osd erasure-code-profile set", "name": "bench",
        "profile": {"plugin": cfg["plugin"], "k": str(s.k),
                    "m": str(s.m),
                    "crush-failure-domain": cfg["failure_domain"]}})
    # the OSDs a degraded mix loses die before the pool exists: the
    # PGs then peer once, with the hole already in every acting set,
    # instead of once whole and again after the kill (peering 128 EC
    # PGs costs tens of seconds of host time, see PERF.md).  The reads
    # meet the same state: each object lacks the dead OSD's shard.
    victims = sorted(int(v) for v in rng.choice(
        sorted(s.cluster.osds), size=int(mix["kill_osds"]),
        replace=False))
    _kill(s, victims)
    s.rados.pool_create("bench", pg_num=int(cfg["pg_num"]),
                        pool_type="erasure",
                        erasure_code_profile="bench")
    s.io = s.rados.open_ioctx("bench")
    s.pool_id = s.rados.pool_lookup("bench")

    if mix["op"] == "write_full":
        # warm-up: one write of the cell's size
        s.io.write_full(s.names[0], s.payloads[0])
        s.last[s.names[0]] = 0
    else:
        # the objects the reads draw from, written at the mix's depth
        depth = int(mix["in_flight"])
        for start in range(0, len(s.names), depth):
            futs = []
            for i, n in enumerate(s.names[start:start + depth]):
                j = (start + i) % len(s.payloads)
                futs.append((n, j, s.io.aio_write_full(n, s.payloads[j])))
            for n, j, f in futs:
                f.wait(float(mix["op_timeout_s"]))
                if f.result < 0:
                    raise RuntimeError(f"prefill of {n}: {f.errno_name}")
                s.last[n] = j
        s.decodes = _decoding_objects(s)
        # warm-up: one read, a degraded one where any object decodes
        warm = next((n for n in s.names if s.decodes[n]), s.names[0])
        s.wrong_reads += s.io.read(warm) != s.payloads[s.last[warm]]
    # the traced window reads the spans of every op it serves
    from collections import deque
    for t in _tracers(s):
        t._done = deque(maxlen=1 << 20)
    return s


def _kill(s, victims: list) -> None:
    """Kill the OSDs and mark them down; nothing is marked out
    (mon_osd_down_out_interval is 600 s and no tick runs)."""
    if not victims:
        return
    for v in victims:
        s.cluster.kill_osd(v)
        s.rados.mon_command({"prefix": "osd down", "ids": [str(v)]})
    end = time.monotonic() + 60
    objecter = s.rados.objecter
    while any(objecter.osdmap.is_up(v) for v in victims):
        if time.monotonic() > end:
            raise RuntimeError(f"osds {victims} never marked down")
        time.sleep(0.05)
    s.dead = victims


def _decoding_objects(s) -> dict:
    """name -> whether a read of it decodes: its acting set has a hole
    (the dead OSD's position) among the k data shards."""
    omap = s.rados.objecter.osdmap
    out = {}
    for n in s.names:
        acting = omap.pg_to_up_acting_osds(
            omap.object_locator_to_pg(n, s.pool_id))[2]
        out[n] = len(acting) < s.k or any(
            not 0 <= o < omap.max_osd or o in s.dead
            for o in acting[:s.k])
    return out


def window(s, seconds: float, probe) -> dict:
    mix = s.mix
    for t in _tracers(s):
        t._done.clear()
    nobj = len(s.names)
    npay = len(s.payloads)
    lock = threading.Lock()
    if mix["op"] == "write_full":
        def target(i):
            # name i mod N; its payload changes on every pass over the
            # names, so a write that stores nothing leaves stale bytes
            return s.names[i % nobj], (i + i // nobj) % npay

        def submit(i):
            name, j = target(i)
            # submitted in the order recorded: per-object ordering then
            # makes s.last the bytes each object ends with
            with lock:
                s.last[name] = j
                return s.io.aio_write_full(name, s.payloads[j])

        def finish(i, fut):
            if fut.result < 0:
                with lock:
                    s.failed_ops += 1
                return False
            return True
    else:
        order = np.random.default_rng([s.seed, 1]).integers(
            0, nobj, size=1 << 20)

        def submit(i):
            return s.io.aio_read(s.names[order[i]])

        def finish(i, fut):
            name = s.names[order[i]]
            if fut.result < 0:
                with lock:
                    s.failed_ops += 1
                return False
            if fut.data != s.payloads[s.last[name]]:
                with lock:
                    s.wrong_reads += 1
                return False
            return True

    prof = threading.Thread(target=_profile, args=(probe, seconds),
                            daemon=True)
    prof.start()
    res = closed_loop(submit, finish, int(mix["in_flight"]), seconds,
                      float(mix["op_timeout_s"]),
                      annotate=probe.annotate if probe.enabled else None)
    prof.join()
    s.loop = res
    done = [op for op in res.ops if op.came]
    ok = [op for op in done if op.ok]
    lat_ms = [(op.end - op.start) * 1e3 for op in res.ops if op.end]
    notes = {"ops": len(res.ops), "ops_ok": len(ok),
             "window_s": res.seconds,
             "op_p50_ms": percentile(lat_ms, 0.50) if lat_ms else None,
             "op_p95_ms": percentile(lat_ms, 0.95) if lat_ms else None,
             "op_max_ms": max(lat_ms) if lat_ms else None,
             "ops_over_2s": sum(v > 2000 for v in lat_ms)}
    if mix["op"] != "write_full":
        notes["decoded_share"] = (sum(s.decodes[s.names[order[op.index]]]
                                      for op in res.ops)
                                  / max(1, len(res.ops)))
    metrics = {
        "client_MBps": len(ok) * s.size / 1e6 / res.seconds,
        "op_p95_ms": notes["op_p95_ms"],
    }
    out = {"metrics": metrics, "attempted": len(res.ops),
           "failed": len(res.ops) - len(ok), "notes": notes}
    if probe.enabled:
        out["spans"] = _spans(s, res.t0)
        out["profiled"] = _profiled_work(s, res, probe,
                                         order if mix["op"] != "write_full"
                                         else None)
    return out


def _profile(probe, seconds: float) -> None:
    """Trace a few steady seconds in the middle of the window."""
    if not probe.enabled:
        return
    time.sleep(min(2.0, seconds / 4))
    probe.start()
    time.sleep(min(5.0, seconds / 2))
    probe.stop()


def _spans(s, t0: float) -> list:
    out = []
    for t in _tracers(s):
        with t._lock:
            spans = list(t._done)
        for sp in spans:
            if sp.start >= t0 and sp.end is not None:
                out.append({"name": sp.name, "span_id": sp.span_id,
                            "parent": sp.parent,
                            "trace_id": sp.trace_id,
                            "start": sp.start, "end": sp.end})
    return out


def _profiled_work(s, res, probe, order) -> dict:
    """The ops served inside the profiled interval, with the least HBM
    bytes each needs by the code's own definition: an encode reads k
    data chunks and writes m parity chunks of every stripe; a decode
    reads k survivors and writes the e erased data chunks."""
    if probe.t0 is None:
        return {}
    k, m = s.k, s.m
    width = k * int(s.cfg["stripe_unit"])
    stripe_bytes = max(1, -(-s.size // width)) * width
    ops = [op for op in res.ops
           if op.came and probe.t0 <= op.end <= probe.t1]
    if s.mix["op"] == "write_full":
        hbm = len(ops) * stripe_bytes * (k + m) / k
        enc = len(ops)
        dec = 0
    else:
        dec = sum(s.decodes[s.names[order[op.index]]] for op in ops)
        hbm = dec * stripe_bytes * (k + len(s.dead)) / k
        enc = 0
    return {"ops": len(ops), "encodes": enc, "decodes": dec,
            "hbm_bytes": hbm, "seconds": probe.t1 - probe.t0}


def check(s) -> dict:
    """Each compared number beside its limit (all exact: limit 0)."""
    never = sum(1 for op in s.loop.ops if not op.came)
    rng = np.random.default_rng([s.seed, 2])
    sample = rng.choice(len(s.names),
                        size=min(int(s.mix["readback_sample"]),
                                 len(s.names)), replace=False)
    readback_wrong = 0
    for j in sample:
        name = s.names[j]
        if name not in s.last:
            continue
        if s.io.read(name) != s.payloads[s.last[name]]:
            readback_wrong += 1
    wrong, missing = _shards(s)
    return {
        "ops_never_completed": {"value": never, "limit": 0},
        "ops_failed": {"value": s.failed_ops, "limit": 0},
        "reads_wrong_bytes": {"value": s.wrong_reads, "limit": 0},
        "readback_wrong_bytes": {"value": readback_wrong, "limit": 0},
        "stored_shards_wrong": {"value": wrong, "limit": 0},
        "stored_shards_missing": {"value": missing, "limit": 0},
    }


def _shards(s) -> tuple[int, int]:
    """Every live OSD's shards of the benchmark's objects against the
    reference encode of each object's last payload.  An object may
    lack only the shards its dead OSDs held."""
    k, m = s.k, s.m
    chunk = int(s.cfg["stripe_unit"])
    want = {}
    for j in sorted(set(s.last.values())):
        want[j] = gf256.shard_streams(s.payloads[j], k, m, chunk)
    found: dict[str, set] = {n: set() for n in s.last}
    wrong = 0
    for d in s.cluster.osds.values():
        st = d.store
        for cid in st.list_collections():
            for oid in st.collection_list(cid):
                if oid.name not in found or oid.shard < 0 or \
                        oid.snap != -2:
                    continue
                data = st.read(cid, oid)
                if data != want[s.last[oid.name]][oid.shard]:
                    wrong += 1
                else:
                    found[oid.name].add(oid.shard)
    allowed = len(s.dead)
    missing = sum(max(0, k + m - len(v) - allowed)
                  for v in found.values())
    return wrong, missing


def teardown(s) -> None:
    if getattr(s, "cluster", None) is not None:
        s.cluster.shutdown()
