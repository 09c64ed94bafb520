"""A Clay (coupled-layer MSR) pool on an in-process MiniCluster, driven
by the same `rados bench`-style closed loop as ec_cluster.

The pool's profile is plugin=clay k m d over a scalar MDS code: every
chunk is q^t sub-chunk planes, and a read that lost one data chunk
rebuilds it from d helpers' q^(t-1) repair planes each (for k=4 m=2
d=5: 4 of 8 planes of 5 helpers).  The window is ec_cluster's; what
differs is the profile, the warm-up (every decode signature on every
primary that meets it), the HBM bytes a decode needs, and the stored
shards `check` holds to benchmark/ref/clay.  A program with no batched
path for the profile exits before any cluster is built.

Configuration keys: those of ec_cluster, plus d, scalar_mds and
technique.  Mix keys: ec_cluster's.
"""
from __future__ import annotations

from collections import deque

import numpy as np

from benchmark.drivers import ec_cluster
from benchmark.ref import clay

teardown = ec_cluster.teardown


def _profile(cfg: dict) -> dict:
    return {"plugin": "clay", "k": str(cfg["k"]), "m": str(cfg["m"]),
            "d": str(cfg["d"]), "scalar_mds": cfg["scalar_mds"],
            "technique": cfg["technique"]}


def setup(cfg: dict, mix: dict, seed: int, trace: bool = False):
    from ceph_tpu.common.options import global_config
    from ceph_tpu.testing import MiniCluster

    if cfg["stripe_unit"] != 4096:
        raise ValueError("the OSD's EC stripe unit is fixed at 4096")
    _same_code(cfg)
    _batched(cfg)
    global_config().set("blkin_trace_all", bool(trace))
    s = ec_cluster.State()
    s.cfg, s.mix, s.seed = cfg, mix, seed
    s.k, s.m, s.d = int(cfg["k"]), int(cfg["m"]), int(cfg["d"])
    rng = np.random.default_rng([seed, 0])
    s.size = int(mix["object_bytes"])
    s.payloads = [rng.bytes(s.size) for _ in range(int(mix["payloads"]))]
    s.names = [f"bench-{j}" for j in range(int(mix["objects"]))]
    s.last: dict[str, int] = {}
    s.dead: list[int] = []
    s.wrong_reads = 0
    s.failed_ops = 0
    s.cluster = MiniCluster(n_osd=int(cfg["osds"]),
                            osds_per_host=int(cfg["osds_per_host"]))
    s.cluster.wait_all_up(timeout=120)
    s.rados = s.cluster.rados(timeout=float(mix["op_timeout_s"]))
    s.rados.mon_command({
        "prefix": "osd erasure-code-profile set", "name": "bench",
        "profile": dict(_profile(cfg),
                        **{"crush-failure-domain": cfg["failure_domain"]})})
    # as in ec_cluster: the OSDs the mix loses die before the pool
    # exists, so the PGs peer once with the hole in every acting set
    victims = sorted(int(v) for v in rng.choice(
        sorted(s.cluster.osds), size=int(mix["kill_osds"]),
        replace=False))
    ec_cluster._kill(s, victims)
    s.rados.pool_create("bench", pg_num=int(cfg["pg_num"]),
                        pool_type="erasure", erasure_code_profile="bench")
    s.io = s.rados.open_ioctx("bench")
    s.pool_id = s.rados.pool_lookup("bench")
    timeout = float(mix["op_timeout_s"])

    if mix["op"] == "write_full":
        s.io.write_full(s.names[0], s.payloads[0])
        s.last[s.names[0]] = 0
    else:
        depth = int(mix["in_flight"])
        for start in range(0, len(s.names), depth):
            futs = []
            for i, n in enumerate(s.names[start:start + depth]):
                j = (start + i) % len(s.payloads)
                futs.append((n, j, s.io.aio_write_full(n, s.payloads[j])))
            for n, j, f in futs:
                f.wait(timeout)
                if f.result < 0:
                    raise RuntimeError(f"prefill of {n}: {f.errno_name}")
                s.last[n] = j
        s.lost, primary = _lost_data_shards(s)
        s.decodes = {n: bool(v) for n, v in s.lost.items()}
        # warm-up: one read of every decode signature (the lost data
        # shards) on every primary that serves one, since each OSD
        # builds its own programs; one plain read where none decodes.
        # At the mix's depth, as the prefill.
        warm = {}
        for n in s.names:
            warm.setdefault((primary[n], s.lost[n]), n)
        warm = list(warm.values())
        for start in range(0, len(warm), depth):
            futs = [(n, s.io.aio_read(n)) for n in warm[start:start + depth]]
            for n, f in futs:
                f.wait(timeout)
                s.wrong_reads += f.result < 0 or \
                    f.data != s.payloads[s.last[n]]
    for t in ec_cluster._tracers(s):
        t._done = deque(maxlen=1 << 20)
    return s


def _same_code(cfg: dict) -> None:
    """Exit before any cluster is built where the program's clay code
    (the plugin's own per-stripe encode, not the timed path) differs
    from the reference's on one stripe: the program does not implement
    this configuration's code, and a run could only end not correct."""
    from ceph_tpu.ec import registry
    ec = registry.factory("clay", _profile(cfg))
    k, m, d = int(cfg["k"]), int(cfg["m"]), int(cfg["d"])
    chunk = int(cfg["stripe_unit"])
    payload = np.random.default_rng(0).bytes(k * chunk)
    got = ec.encode(set(range(k + m)), payload)
    want = clay.shard_streams(payload, k, m, d, chunk)
    if [bytes(np.asarray(got[i])) for i in range(k + m)] != want:
        raise SystemExit("benchmark: the program's clay code differs "
                         "from benchmark/ref/clay's")


def _batched(cfg: dict) -> None:
    """Exit before any cluster is built where the program serves this
    pool only stripe by stripe in the interpreter (no batched clay
    API): there the prefill and every decode take seconds of host time
    per 4 MiB object, and one run lasts about 6.5 minutes on a v5e
    host, far past the window it is meant to measure."""
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil
    if not ecutil.batchable(registry.factory("clay", _profile(cfg))):
        raise SystemExit("benchmark: the program has no batched path for "
                         "this clay profile; the cell is not run on its "
                         "per-stripe host path")


def _lost_data_shards(s) -> tuple[dict, dict]:
    """(name -> the data shards whose OSD is dead or missing from the
    object's acting set, name -> the acting primary)."""
    omap = s.rados.objecter.osdmap
    lost, primary = {}, {}
    for n in s.names:
        _, _, acting, prim = omap.pg_to_up_acting_osds(
            omap.object_locator_to_pg(n, s.pool_id))
        lost[n] = tuple(p for p in range(s.k)
                        if p >= len(acting) or acting[p] in s.dead
                        or not 0 <= acting[p] < omap.max_osd)
        primary[n] = prim
    return lost, primary


def _stripe_loop_calls(s):
    """EC calls the live OSDs ran in the per-stripe host loop, or None
    where the program keeps no such counter."""
    try:
        return sum(d.perf.get("ec_stripe_loop_calls")
                   for d in s.cluster.osds.values())
    except KeyError:
        return None


def window(s, seconds: float, probe) -> dict:
    """ec_cluster's window.  A traced run's decode bytes are the repair
    plan's: one lost data chunk (one OSD down) rebuilt from d helpers'
    1/q of a chunk each, so stripe bytes x (d/q + e)/k.  The notes add
    the per-stripe host loops the window ran."""
    loops = _stripe_loop_calls(s)
    out = ec_cluster.window(s, seconds, probe)
    if loops is not None:
        out["notes"]["ec_stripe_loop_calls"] = _stripe_loop_calls(s) - loops
    prof = out.get("profiled")
    if prof and prof.get("decodes") and s.dead:
        width = s.k * int(s.cfg["stripe_unit"])
        stripe_bytes = max(1, -(-s.size // width)) * width
        lost = next(v for v in s.lost.values() if v)
        q = clay.params(s.k, s.m, s.d)[0]
        prof["hbm_bytes"] = prof["decodes"] * stripe_bytes \
            * (s.d / q + len(lost)) / s.k
    return out


def check(s) -> dict:
    """Each compared number beside its limit (all exact: limit 0)."""
    never = sum(1 for op in s.loop.ops if not op.came)
    rng = np.random.default_rng([s.seed, 2])
    sample = rng.choice(len(s.names),
                        size=min(int(s.mix["readback_sample"]),
                                 len(s.names)), replace=False)
    readback_wrong = sum(
        s.io.read(s.names[j]) != s.payloads[s.last[s.names[j]]]
        for j in sample if s.names[j] in s.last)
    wrong, missing = _shards(s)
    return {
        "ops_never_completed": {"value": never, "limit": 0},
        "ops_failed": {"value": s.failed_ops, "limit": 0},
        "reads_wrong_bytes": {"value": s.wrong_reads, "limit": 0},
        "readback_wrong_bytes": {"value": int(readback_wrong), "limit": 0},
        "stored_shards_wrong": {"value": wrong, "limit": 0},
        "stored_shards_missing": {"value": missing, "limit": 0},
    }


def _shards(s) -> tuple[int, int]:
    """Every live OSD's shards of the benchmark's objects against the
    reference clay encode of each object's last payload.  An object
    may lack only the shards its dead OSDs held."""
    chunk = int(s.cfg["stripe_unit"])
    want = {j: clay.shard_streams(s.payloads[j], s.k, s.m, s.d, chunk)
            for j in sorted(set(s.last.values()))}
    found: dict[str, set] = {n: set() for n in s.last}
    wrong = 0
    for d in s.cluster.osds.values():
        st = d.store
        for cid in st.list_collections():
            for oid in st.collection_list(cid):
                if oid.name not in found or oid.shard < 0 or \
                        oid.snap != -2:
                    continue
                if st.read(cid, oid) != want[s.last[oid.name]][oid.shard]:
                    wrong += 1
                else:
                    found[oid.name].add(oid.shard)
    n = s.k + s.m
    missing = sum(max(0, n - len(v) - len(s.dead)) for v in found.values())
    return wrong, missing
