"""librbd async IO (ref: librbd::io::ImageRequestWQ, AioCompletion):
`Image.aio_write`/`aio_read` submit every object op of a request and
return; the completion fires once all of them are back, with the first
failure as its result.  Requests overlap at the objecter while each
object keeps its submission order."""
import numpy as np
import pytest

from ceph_tpu.client.rados import RadosError
from ceph_tpu.common.options import global_config
from ceph_tpu.common.perf_counters import global_perf
from ceph_tpu.rbd import RBD, Image
from ceph_tpu.testing import MiniCluster

ORDER = 16                       # 64 KiB objects
SIZE = 1 << 20                   # 16 objects


@pytest.fixture(scope="module")
def cluster():
    conf = global_config()
    cache = conf["rbd_cache"]
    conf.set("rbd_cache", False)  # the write-through path, as fio runs it
    c = MiniCluster(n_osd=4, threaded=True)
    c.wait_all_up()
    c.rados().pool_create("rbd", pg_num=8)
    yield c
    c.shutdown()
    conf.set("rbd_cache", cache)


@pytest.fixture
def image(cluster, request):
    io = cluster.rados().open_ioctx("rbd")
    name = request.node.name.replace("[", "-").replace("]", "")
    RBD().create(io, name, size=SIZE, order=ORDER)
    img = Image(io, name)
    yield img
    img.close()


def test_16_concurrent_writes_complete_and_overlap(image):
    """16 writes in flight on one image all land, and the objecter had
    more than one of their object ops on the wire at once."""
    objecter = image.ioctx.rados.objecter
    seen = []
    send = objecter._send_op

    def counting_send(op):
        seen.append(len(objecter.in_flight))
        return send(op)

    objecter._send_op = counting_send
    rng = np.random.default_rng(5_000_000_101)
    model = bytearray(SIZE)
    try:
        comps = []
        for block in rng.choice(SIZE // 4096, size=16, replace=False):
            data = rng.bytes(4096)
            model[block * 4096:(block + 1) * 4096] = data
            comps.append(image.aio_write(int(block) * 4096, data))
        for c in comps:
            assert c.wait(30).result == 4096
    finally:
        objecter._send_op = send
    assert max(seen) > 1
    assert image.read(0, SIZE) == bytes(model)
    c = image.aio_read(0, SIZE).wait(30)
    assert c.result == SIZE and c.data == bytes(model)


def test_overlapping_writes_keep_submission_order(image):
    rng = np.random.default_rng(11)
    model = bytearray(SIZE)
    comps = []
    for _ in range(12):
        off = int(rng.integers(0, 3 * 4096))
        data = rng.bytes(int(rng.integers(1, 3 * 4096)))
        model[off:off + len(data)] = data
        comps.append(image.aio_write(off, data))
    for c in comps:
        c.wait(30)
    assert image.read(0, 6 * 4096) == bytes(model[:6 * 4096])


def test_failed_object_op_is_the_request_result(image, monkeypatch):
    """A write over two objects whose second object op fails (its pool
    is gone) completes with that op's error; `write` raises it."""
    dio = image._dio
    submit = dio.aio_write
    calls = []

    def second_fails(oid, data, offset=0):
        calls.append(oid)
        if len(calls) == 2:
            return dio.rados.objecter.submit(1 << 20, oid, "write",
                                             offset=offset, data=data)
        return submit(oid, data, offset)

    monkeypatch.setattr(dio, "aio_write", second_fails)
    span = 1 << ORDER
    c = image.aio_write(span - 100, b"z" * 200).wait(30)
    assert c.result == -2 and c.errno_name == "ENOENT"
    calls.clear()
    with pytest.raises(RadosError):
        image.write(span - 100, b"z" * 200)


def test_journaling_image_appends_before_the_data_write(cluster):
    io = cluster.rados().open_ioctx("rbd")
    RBD().create(io, "journaled", size=SIZE, order=ORDER,
                 journaling=True)
    img = Image(io, "journaled")
    events = []
    append = img._journal.append
    write = img._dio.aio_write
    img._journal.append = lambda tag, data: (events.append(tag),
                                             append(tag, data))[1]
    img._dio.aio_write = lambda oid, *a, **kw: (
        events.append(oid.split(".")[0]), write(oid, *a, **kw))[1]
    img.aio_write(10, b"journal first").wait(30)
    # the event, the object map's pre-update, then the data object
    assert events == ["write", "rbd_object_map", "rbd_data"]
    assert img.read(10, 13) == b"journal first"
    img.close()


def test_request_span_parents_its_object_ops(image):
    conf = global_config()
    conf.set("blkin_trace_all", True)
    try:
        tracer = image.ioctx.rados.objecter.tracer
        span = 1 << ORDER
        image.aio_write(span - 100, b"s" * 200).wait(30)
        image.aio_read(span - 50, 100).wait(30)
        done = list(tracer._done)
    finally:
        conf.set("blkin_trace_all", False)
    for name, op, n in (("rbd_aio_write", "objecter_op:write", 2),
                        ("rbd_aio_read", "objecter_op:read", 2)):
        req = [s for s in done if s.name == name][-1]
        kids = [s for s in done if s.parent == req.span_id]
        assert [k.name for k in kids] == [op] * n
        assert all(req.start <= k.start and k.end <= req.end
                   for k in kids)


def test_perf_counters_under_librbd_names(image):
    image.write(0, b"p" * 5000)
    image.read(0, 5000)
    pc = global_perf().perf_dump()[f"librbd-rbd-{image.name}"]
    assert pc["wr"] == 1 and pc["wr_bytes"] == 5000
    assert pc["rd"] == 1 and pc["rd_bytes"] == 5000
    assert pc["wr_latency"]["avgcount"] == 1
    assert pc["rd_latency"]["avgcount"] == 1


def test_concurrent_submitters_lose_no_completion(image):
    """More submitting threads than cores, with a short switch interval:
    every completion fires once, the in-flight count returns to zero
    (a lost update would leave `flush` waiting) and every block holds
    its writer's bytes."""
    import os
    import sys
    import threading
    blocks = SIZE // 4096
    nthreads = min(2 * (os.cpu_count() or 4) + 1, blocks)
    per = min(4, blocks // nthreads)
    model = bytearray(SIZE)
    errors = []

    def writer(t):
        try:
            for block in range(t * per, (t + 1) * per):
                data = bytes([t % 251 + 1]) * 4096
                model[block * 4096:(block + 1) * 4096] = data
                assert image.aio_write(block * 4096, data).wait(
                    60).result == 4096
        except Exception as ex:       # reported by the main thread
            errors.append(ex)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer, args=(t,))
                   for t in range(nthreads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
    finally:
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    assert errors == []
    image.flush()
    assert image._aio_writes == 0 and image._aio_idle.is_set()
    assert image.read(0, SIZE) == bytes(model)
