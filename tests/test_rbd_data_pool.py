"""RBD images with a separate data pool (`rbd create --data-pool`, ref:
librbd ImageCtx::data_ctx): the data objects live in an erasure-coded
pool, the header and object map in the image's replicated pool, and
every image operation behaves as a plain bytearray model of the image
says, with the write-back cache off and on.  Snapshots are refused on
such an image: erasure-coded pools take none."""
import numpy as np
import pytest

from ceph_tpu.common.options import global_config
from ceph_tpu.rbd import RBD, Image
from ceph_tpu.rbd.image import RBDError, header_name
from ceph_tpu.testing import MiniCluster

ORDER = 15                       # 32 KiB objects, 4 EC stripes each
SIZE = 8 << ORDER


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster(n_osd=5, threaded=True)
    c.wait_all_up()
    r = c.rados()
    r.pool_create("rbd", pg_num=8)
    r.mon_command({"prefix": "osd erasure-code-profile set",
                   "name": "k2m2",
                   "profile": {"plugin": "tpu", "k": "2", "m": "2",
                               "crush-failure-domain": "host"}})
    r.pool_create("ecdata", pg_num=8, pool_type="erasure",
                  erasure_code_profile="k2m2")
    yield c
    c.shutdown()


@pytest.fixture(params=[False, True], ids=["nocache", "cache"])
def io(cluster, request):
    conf = global_config()
    old = conf["rbd_cache"]
    conf.set("rbd_cache", request.param)
    yield cluster.rados().open_ioctx("rbd")
    conf.set("rbd_cache", old)


def _name(request) -> str:
    return request.node.name.replace("[", "-").replace("]", "")


def _pools(io, name: str) -> tuple[set, set]:
    """(names in the image pool, names in the data pool) of `name`."""
    ec = io.rados.open_ioctx("ecdata")
    mine = lambda objs: {o for o in objs if name in o}  # noqa: E731
    return mine(io.list_objects()), mine(ec.list_objects())


def _mutate(img, model: bytearray, rng, n: int = 12) -> None:
    for _ in range(n):
        off = int(rng.integers(0, len(model) - 1))
        data = rng.bytes(int(rng.integers(1, min(3 << ORDER,
                                                 len(model) - off))))
        img.write(off, data)
        model[off:off + len(data)] = data


def test_io_discard_resize_and_placement_follow_the_model(io, request):
    name = _name(request)
    RBD().create(io, name, SIZE, order=ORDER, data_pool="ecdata")
    img = Image(io, name)
    rng = np.random.default_rng(5_000_000_201)
    model = bytearray(SIZE)
    _mutate(img, model, rng)
    assert img.read(0, SIZE) == bytes(model)
    # discard: a whole object, and a partial range
    img.discard(2 << ORDER, 1 << ORDER)
    model[2 << ORDER:3 << ORDER] = bytes(1 << ORDER)
    img.discard(100, 5000)
    model[100:5100] = bytes(5000)
    assert img.read(0, SIZE) == bytes(model)
    # shrink drops the tail objects; growing reads zeros there
    img.resize(5 << ORDER)
    del model[5 << ORDER:]
    img.resize(SIZE)
    model += bytes(SIZE - len(model))
    assert img.read(0, SIZE) == bytes(model)
    img.flush()
    meta, data = _pools(io, name)
    assert meta == {header_name(name), f"rbd_object_map.{name}"}
    assert data and all(o.startswith(f"rbd_data.{name}.") for o in data)
    img.close()
    RBD().remove(io, name)
    assert _pools(io, name) == (set(), set())


def test_snapshot_on_an_erasure_coded_data_pool_is_refused(io, request):
    """Erasure-coded pools take no snapshots, so neither does an image
    whose data lives in one: snap_create fails before it journals,
    allocates a snapid or touches the object map, and the image goes on
    as the model says."""
    name = _name(request)
    RBD().create(io, name, SIZE, order=ORDER, data_pool="ecdata")
    img = Image(io, name)
    rng = np.random.default_rng(5_000_000_202)
    model = bytearray(SIZE)
    _mutate(img, model, rng)
    ec = io.rados.open_ioctx("ecdata")
    seq = ec.rados.objecter.osdmap.pools[ec.pool_id].snap_seq
    with pytest.raises(RBDError) as ex:
        img.snap_create("s1")
    assert ex.value.errno == 95
    assert img.snap_list() == [] and img._dio.write_snapc is None
    assert ec.rados.objecter.osdmap.pools[ec.pool_id].snap_seq == seq
    _mutate(img, model, rng)
    assert img.read(0, SIZE) == bytes(model)
    img.close()
    reopened = Image(io, name)
    assert reopened.snap_list() == []
    reopened.close()
    meta, _ = _pools(io, name)
    assert meta == {header_name(name), f"rbd_object_map.{name}"}


def test_clone_into_a_data_pool_follows_the_model(io, request):
    """A child on the EC data pool of a parent whose data sits beside
    its header: reads fall through to the parent snapshot, and each
    copy-up lands in the child's data pool."""
    parent, child = _name(request) + "-p", _name(request) + "-c"
    RBD().create(io, parent, SIZE, order=ORDER)
    p = Image(io, parent)
    rng = np.random.default_rng(5_000_000_203)
    model = bytearray(SIZE)
    _mutate(p, model, rng)
    p.snap_create("base")
    p.snap_protect("base")
    p.close()
    RBD().clone(io, parent, "base", io, child, data_pool="ecdata")
    c = Image(io, child)
    assert c.read(0, SIZE) == bytes(model)       # all from the parent
    _mutate(c, model, rng, n=6)                  # copy-ups, then writes
    assert c.read(0, SIZE) == bytes(model)
    c.flush()
    meta, data = _pools(io, child)
    assert not any(o.startswith("rbd_data.") for o in meta)
    assert data and all(o.startswith(f"rbd_data.{child}.") for o in data)
    c.close()


def test_no_data_pool_keeps_data_beside_the_header(io, request):
    name = _name(request)
    RBD().create(io, name, SIZE, order=ORDER)
    img = Image(io, name)
    assert img.data_pool is None and img._dio is img._wio
    model = bytearray(SIZE)
    _mutate(img, model, np.random.default_rng(5_000_000_204))
    assert img.read(0, SIZE) == bytes(model)
    img.flush()
    meta, data = _pools(io, name)
    assert data == set()
    assert any(o.startswith(f"rbd_data.{name}.") for o in meta)
    assert b"data_pool" not in io.read(header_name(name))
    img.close()
