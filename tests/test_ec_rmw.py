"""ECBackend's read-modify-write on the batched device path (the tpu
plugin, k=8 m=4, under JAX on the CPU): 4 KiB overwrites of a
prefilled object read their stripe back, merge and re-encode it in one
dispatch, and leave every shard equal to the plain reference's encode
of the merged bytes.  The RMW counters and the `ec_rmw_read` span
count and time exactly those writes."""
import numpy as np
import pytest

from benchmark.ref import gf256
from ceph_tpu.common.perf_counters import PerfCounters
from ceph_tpu.common.tracing import Tracer, new_trace
from ceph_tpu.osd.ec_backend import ECBackend, pg_cid
from ceph_tpu.store import ObjectId

from test_ec_backend import Cluster

K, M, CHUNK = 8, 4, 4096
STRIPE = K * CHUNK
OBJ = 16 * STRIPE                # a prefilled object of 16 stripes


@pytest.fixture
def cl():
    c = Cluster(k=K, m=M)
    c.backend.perf = PerfCounters("ec-rmw-test")
    for key in ECBackend.PERF_KEYS:
        c.backend.perf.add_u64_counter(key)
    c.backend.tracer = Tracer("osd.0")
    return c


def _shards_match(cl, oid: str, model: bytes) -> None:
    want = gf256.shard_streams(model, K, M, CHUNK)
    for s in range(K + M):
        got = cl.stores[s].read(pg_cid("1.0"), ObjectId(oid, shard=s))
        assert got == want[s], f"shard {s}"


def test_seeded_4k_overwrites_match_the_reference_encode(cl):
    rng = np.random.default_rng(5_000_000_017)
    model = bytearray(rng.bytes(OBJ))
    assert cl.write("obj", 0, bytes(model))
    base = cl.backend.perf.dump()
    assert base["ec_rmw_reads"] == 0          # a fresh object reads nothing
    offsets = rng.choice(OBJ // 4096, size=24, replace=False) * 4096
    for off in offsets:
        data = rng.bytes(4096)
        ctx = new_trace()
        done = []
        cl.backend.submit_transaction(
            "obj", [("write", int(off), data)], done.append, trace=ctx)
        assert done == [True]
        model[off:off + 4096] = data
    _shards_match(cl, "obj", bytes(model))
    assert cl.read("obj") == bytes(model)
    perf = cl.backend.perf.dump()
    assert perf["ec_rmw_reads"] == len(offsets)
    assert perf["ec_rmw_read_bytes"] == len(offsets) * STRIPE
    assert perf["ec_stripe_loop_calls"] == 0
    assert perf["ec_dispatches"] >= len(offsets) + 1


def test_rmw_read_span_is_a_child_of_the_op(cl):
    payload = np.random.default_rng(1).bytes(OBJ)
    ctx = new_trace()
    cl.backend.submit_transaction("obj", [("write", 0, payload)],
                                  lambda ok: None, trace=ctx)
    spans = [s for s in cl.backend.tracer._done if s.name == "ec_rmw_read"]
    assert spans == []                    # a whole-object write reads nothing
    ctx = new_trace()
    cl.backend.submit_transaction("obj", [("write", 8192, b"x" * 4096)],
                                  lambda ok: None, trace=ctx)
    spans = [s for s in cl.backend.tracer._done if s.name == "ec_rmw_read"]
    assert len(spans) == 1
    assert spans[0].parent == ctx["span"]
    assert spans[0].trace_id == ctx["trace_id"]
    assert spans[0].end >= spans[0].start


def test_concurrent_rmws_into_one_stripe_apply_in_order(cl):
    """Three overlapping writes into one stripe, submitted while the
    first one's reads are held back: each merges over the one before
    it, in submission order."""
    rng = np.random.default_rng(7)
    model = bytearray(rng.bytes(OBJ))
    assert cl.write("obj", 0, bytes(model))
    cl.defer(1)                           # a data shard answers late
    done = []
    writes = [(STRIPE + 100, rng.bytes(4096)),
              (STRIPE + 2048, rng.bytes(4096)),
              (STRIPE, rng.bytes(512))]
    for off, data in writes:
        cl.backend.submit_transaction(
            "obj", [("write", off, data)],
            lambda ok, off=off: done.append((off, ok)))
    assert done == []
    while cl.deferred.get(1):
        cl.flush(1)
    cl.deferred.pop(1, None)
    assert done == [(off, True) for off, _ in writes]
    for off, data in writes:
        model[off:off + len(data)] = data
    _shards_match(cl, "obj", bytes(model))
    assert cl.backend.perf.dump()["ec_rmw_reads"] == len(writes)
