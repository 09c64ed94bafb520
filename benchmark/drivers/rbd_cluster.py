"""An RBD image whose data objects live on an erasure-coded pool
(`rbd create --data-pool`), on an in-process MiniCluster, driven the
way fio's rbd engine is under cbt's librbdfio: a prefill of the whole
image, then 4 KiB random writes, a closed loop at the mix's depth.

The window's entry is Image.aio_write: librbd (striping, exclusive
lock, object map, one object op) -> objecter -> OSD primary ->
ECBackend's read-modify-write (the stripe the write only partly covers
is read back from its k data shards, merged and re-encoded) -> ecutil
-> the tpu plugin -> device -> MemStore.  The header and object map
live in a replicated pool.  `check` holds the image to
benchmark/ref/rbd: sampled blocks read back with their whole stripe,
every live shard of their objects against the reference encode, and
no object in the wrong pool.  A program without a data pool or image
aio exits before any cluster is built.

Configuration keys: those of ec_cluster, plus metadata_pool {pg_num,
size}, image {name, size, order} and rbd_cache.  Mix keys: block_bytes,
in_flight, payloads, prefill_bytes, prefill_in_flight, warmup_ops,
op_timeout_s, readback_sample.
"""
from __future__ import annotations

import threading
from collections import deque

import numpy as np

from benchmark.drivers import ec_cluster
from benchmark.loop import closed_loop, percentile
from benchmark.ref import rbd as ref

teardown = ec_cluster.teardown

DATA_POOL = "rbd-data"
META_POOL = "rbd"


def _image_api() -> None:
    """Exit before any cluster is built where the program's librbd has
    no data pool or no image aio: the cell cannot run there."""
    import inspect
    from ceph_tpu.rbd import RBD, Image
    if "data_pool" not in inspect.signature(RBD.create).parameters or \
            not hasattr(Image, "aio_write"):
        raise SystemExit("benchmark: the program's librbd has no "
                         "data_pool or no Image.aio_write")


def setup(cfg: dict, mix: dict, seed: int, trace: bool = False):
    from ceph_tpu.common.options import global_config
    from ceph_tpu.testing import MiniCluster

    if cfg["stripe_unit"] != 4096:
        raise ValueError("the OSD's EC stripe unit is fixed at 4096")
    _image_api()
    from ceph_tpu.rbd import RBD, Image
    conf = global_config()
    conf.set("blkin_trace_all", bool(trace))
    conf.set("rbd_cache", bool(cfg["rbd_cache"]))
    s = ec_cluster.State()
    s.cfg, s.mix, s.seed = cfg, mix, seed
    s.k, s.m = int(cfg["k"]), int(cfg["m"])
    img = cfg["image"]
    s.order = int(img["order"])
    s.size = int(img["size"])
    s.block = int(mix["block_bytes"])
    s.stripe = s.k * int(cfg["stripe_unit"])
    rng = np.random.default_rng([seed, 0])
    s.payloads = [rng.bytes(s.block) for _ in range(int(mix["payloads"]))]
    # fio's random map: every block once, in a seeded order
    s.blocks = np.random.default_rng([seed, 1]).permutation(
        s.size // s.block)
    s.model = ref.Image(seed, s.size, s.order)
    s.written: dict[int, int] = {}       # block -> payload index
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
    s.rados.pool_create(DATA_POOL, pg_num=int(cfg["pg_num"]),
                        pool_type="erasure", erasure_code_profile="bench")
    meta = cfg["metadata_pool"]
    s.rados.pool_create(META_POOL, pg_num=int(meta["pg_num"]))
    pools = s.rados.objecter.osdmap.pools
    if pools[s.rados.pool_lookup(META_POOL)].size != int(meta["size"]) \
            or pools[s.rados.pool_lookup(DATA_POOL)].min_size != \
            int(cfg["min_size"]):
        raise ValueError("the pools' sizes are not the configuration's")
    s.io = s.rados.open_ioctx(META_POOL)
    s.data_io = s.rados.open_ioctx(DATA_POOL)
    s.name = img["name"]
    RBD().create(s.io, s.name, s.size, order=s.order,
                 data_pool=DATA_POOL)
    s.image = Image(s.io, s.name)
    timeout = float(mix["op_timeout_s"])
    _prefill(s, int(mix["prefill_bytes"]), int(mix["prefill_in_flight"]),
             timeout)
    # warm-up: the window's first blocks, at its depth, so the one
    # stripe encode and the read-back are compiled and cached
    s.next = int(mix["warmup_ops"])
    for c in [_submit(s, i) for i in range(s.next)]:
        c.wait(timeout)
        if c.result < 0:
            raise RuntimeError(f"warm-up write: {c.errno_name}")
    for t in ec_cluster._tracers(s):
        t._done = deque(maxlen=1 << 20)
    return s


def _prefill(s, chunk: int, depth: int, timeout: float) -> None:
    """cbt's prefill: the whole image in sequential writes of `chunk`
    bytes, `depth` in flight."""
    obj = 1 << s.order
    pend = deque()
    for off in range(0, s.size, chunk):
        data = b"".join(ref.prefill(s.seed, o, obj)
                        for o in range(off >> s.order,
                                       (off + chunk) >> s.order))
        pend.append(s.image.aio_write(off, data))
        if len(pend) >= depth:
            _done(pend.popleft(), timeout)
    while pend:
        _done(pend.popleft(), timeout)


def _done(c, timeout: float) -> None:
    c.wait(timeout)
    if c.result < 0:
        raise RuntimeError(f"prefill write: {c.errno_name}")


def _submit(s, i: int):
    """Write op i: the i-th block of the permutation, payload i mod
    payloads, recorded at submission: the image serializes
    submissions and the objecter keeps each object's order, so the
    last payload recorded for a block is the one it ends with."""
    block = int(s.blocks[i % len(s.blocks)])
    j = i % len(s.payloads)
    s.written[block] = j
    return s.image.aio_write(block * s.block, s.payloads[j])


def _osd_counter(s, key: str) -> int:
    return sum(int(d.perf.get(key)) for d in s.cluster.osds.values())


def window(s, seconds: float, probe) -> dict:
    for t in ec_cluster._tracers(s):
        t._done.clear()
    lock = threading.Lock()
    base = s.next
    rmw0 = _osd_counter(s, "ec_rmw_reads")
    loops0 = _osd_counter(s, "ec_stripe_loop_calls")

    def submit(i):
        with lock:
            return _submit(s, base + i)

    def finish(i, comp):
        if comp.result < 0:
            with lock:
                s.failed_ops += 1
            return False
        return True

    # object ops in flight at the objecter, sampled through the window
    in_flight = []
    stop = threading.Event()

    def sample():
        objecter = s.rados.objecter
        while not stop.wait(0.01):
            in_flight.append(len(objecter.in_flight))

    sampler = threading.Thread(target=sample, daemon=True)
    prof = threading.Thread(target=ec_cluster._profile,
                            args=(probe, seconds), daemon=True)
    sampler.start()
    prof.start()
    res = closed_loop(submit, finish, int(s.mix["in_flight"]), seconds,
                      float(s.mix["op_timeout_s"]),
                      annotate=probe.annotate if probe.enabled else None)
    stop.set()
    prof.join()
    sampler.join()
    s.loop = res
    ok = [op for op in res.ops if op.came and op.ok]
    lat_ms = [(op.end - op.start) * 1e3 for op in res.ops if op.end]
    notes = {"ops": len(res.ops), "ops_ok": len(ok),
             "window_s": res.seconds,
             "op_p50_ms": percentile(lat_ms, 0.50) if lat_ms else None,
             "op_p95_ms": percentile(lat_ms, 0.95) if lat_ms else None,
             "op_max_ms": max(lat_ms) if lat_ms else None,
             "ec_rmw_reads_per_op": (_osd_counter(s, "ec_rmw_reads")
                                     - rmw0) / max(1, len(res.ops)),
             "objecter_ops_in_flight_mean":
                 sum(in_flight) / len(in_flight) if in_flight else None,
             "ec_stripe_loop_calls":
                 _osd_counter(s, "ec_stripe_loop_calls") - loops0}
    metrics = {"client_MBps": len(ok) * s.block / 1e6 / res.seconds,
               "op_p95_ms": notes["op_p95_ms"]}
    out = {"metrics": metrics, "attempted": len(res.ops),
           "failed": len(res.ops) - len(ok), "notes": notes}
    if probe.enabled:
        out["spans"] = ec_cluster._spans(s, res.t0)
        out["profiled"] = _profiled_work(s, res, probe)
    return out


def _profiled_work(s, res, probe) -> dict:
    """The writes served inside the profiled interval, each with the
    least HBM bytes its encode needs: the stripe it re-encodes, k data
    chunks in and m parity chunks out (not the 4 KiB it carries)."""
    if probe.t0 is None:
        return {}
    ops = [op for op in res.ops
           if op.came and probe.t0 <= op.end <= probe.t1]
    stripes = -(-s.block // s.stripe)
    return {"ops": len(ops), "encodes": len(ops), "decodes": 0,
            "hbm_bytes": len(ops) * stripes * s.stripe
            * (s.k + s.m) / s.k,
            "seconds": probe.t1 - probe.t0}


def check(s) -> dict:
    """Each compared number beside its limit (all exact: limit 0)."""
    never = sum(1 for op in s.loop.ops if not op.came)
    written = sorted(s.written)
    for block in written:
        s.model.write(block * s.block, s.payloads[s.written[block]])
    rng = np.random.default_rng([s.seed, 2])
    sample = [written[j] for j in rng.choice(
        len(written), size=min(int(s.mix["readback_sample"]),
                               len(written)), replace=False)]
    readback_wrong = 0
    for block in sample:
        start = block * s.block // s.stripe * s.stripe
        if s.image.read(start, s.stripe) != s.model.read(start,
                                                         s.stripe):
            readback_wrong += 1
    objects = sorted({block * s.block >> s.order for block in sample})
    wrong, missing = _shards(s, objects)
    return {
        "ops_never_completed": {"value": never, "limit": 0},
        "ops_failed": {"value": s.failed_ops, "limit": 0},
        "readback_wrong_stripes": {"value": readback_wrong, "limit": 0},
        "stored_shards_wrong": {"value": wrong, "limit": 0},
        "stored_shards_missing": {"value": missing, "limit": 0},
        "objects_in_wrong_pool": {"value": _misplaced(s), "limit": 0},
    }


def _shards(s, objects: list) -> tuple[int, int]:
    """Every OSD's shards of the sampled data objects against the
    reference encode of each object's bytes; each object has all k+m."""
    chunk = int(s.cfg["stripe_unit"])
    names = {ref.data_name(s.name, o): o for o in objects}
    want = {o: s.model.shards(o, s.k, s.m, chunk) for o in objects}
    found = {o: set() for o in objects}
    wrong = 0
    for d in s.cluster.osds.values():
        st = d.store
        for cid in st.list_collections():
            for oid in st.collection_list(cid):
                o = names.get(oid.name)
                if o is None or oid.shard < 0 or oid.snap != -2:
                    continue
                if st.read(cid, oid) != want[o][oid.shard]:
                    wrong += 1
                else:
                    found[o].add(oid.shard)
    missing = sum(s.k + s.m - len(v) for v in found.values())
    return wrong, missing


def _misplaced(s) -> int:
    """Data objects outside the data pool, and anything else in it."""
    prefix = f"rbd_data.{s.name}."
    meta = sum(n.startswith(prefix) for n in s.io.list_objects())
    data = sum(not n.startswith(prefix) for n in s.data_io.list_objects())
    return meta + data
