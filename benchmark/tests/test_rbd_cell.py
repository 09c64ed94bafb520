"""The RBD-on-EC cell on the CPU at a tiny size: its reference against
a layout and an encode worked by hand, its metric readers on
hand-built spans, a sound run correct (traced and not), a program
without a data pool stopped before any cluster, and a run with the
timed path broken underneath not correct."""
import numpy as np
import pytest

from benchmark import faults, run
from benchmark.ref import rbd
from benchmark.tests.tiny import SPEC, run_tiny

CELL = "rbd-ec-k8m4.randwrite-4k"


def tiny_rbd() -> tuple[dict, dict]:
    c = run.Cell(SPEC, CELL)
    cfg, mix = dict(c.config), dict(c.mix)
    cfg["pg_num"] = 8
    cfg["metadata_pool"] = dict(cfg["metadata_pool"], pg_num=4)
    cfg["image"] = dict(cfg["image"], size=4 << 20, order=20)
    mix.update(prefill_bytes=1 << 20, prefill_in_flight=4, in_flight=4,
               warmup_ops=4, payloads=8, readback_sample=4)
    return cfg, mix


def test_layout_is_rbds_default():
    assert rbd.locate(5 * (4 << 20) + 12345, 22) == (5, 12345)
    assert rbd.locate((4 << 20) - 1, 22) == (0, (4 << 20) - 1)
    assert rbd.data_name("image_name", 10) == \
        "rbd_data.image_name.000000000000000a"


def test_model_overlays_writes_on_the_prefill():
    img = rbd.Image(seed=9, size=4 << 16, order=16)
    obj1 = rbd.prefill(9, 1, 1 << 16)
    img.write((1 << 16) - 2, b"abcd")      # across objects 0 and 1
    assert img.read((1 << 16) - 2, 4) == b"abcd"
    assert img.read(1 << 16, 10) == b"cd" + obj1[2:10]
    assert img.object_bytes(3) == rbd.prefill(9, 3, 1 << 16)


def _xtime(a: np.ndarray) -> np.ndarray:
    """Multiply by 2 in GF(2^8) mod x^8+x^4+x^3+x^2+1."""
    a = a.astype(np.uint16) << 1
    return (np.where(a & 0x100, a ^ 0x11D, a)).astype(np.uint8)


def test_shards_are_data_chunks_and_the_first_two_parity_rows():
    """k=8 m=4 reed_sol_van over 4 KiB chunks of a two-stripe object:
    shard i < 8 holds chunk i of each stripe; the first coding row of
    ISA-L's matrix is all ones (XOR); the second is 1, 2, 4, ... 128,
    summed here by Horner's rule with xtime."""
    img = rbd.Image(seed=11, size=2 << 16, order=16)
    img.write(100, b"written")
    obj = np.frombuffer(img.object_bytes(0), np.uint8)
    assert obj[100:107].tobytes() == b"written"
    shards = img.shards(0, 8, 4, 4096)
    chunks = obj.reshape(2, 8, 4096)           # stripe, chunk, byte
    for i in range(8):
        assert shards[i] == chunks[:, i].tobytes()
    xor = np.bitwise_xor.reduce(chunks, axis=1)
    assert shards[8] == xor.tobytes()
    acc = np.zeros((2, 4096), np.uint8)
    for j in reversed(range(8)):
        acc = _xtime(acc) ^ chunks[:, j]
    assert shards[9] == acc.tobytes()


class _Run:
    def __init__(self, spans):
        self.spans = spans


def _span(name, sid, parent, start, end):
    return {"name": name, "span_id": sid, "parent": parent,
            "trace_id": "t", "start": start, "end": end}


def test_metric_readers_on_hand_built_spans():
    spans = [
        _span("rbd_aio_write", "a", None, 0.0, 1.0),
        _span("objecter_op:write", "a1", "a", 0.1, 0.5),
        _span("objecter_op:write", "a2", "a", 0.3, 0.7),
        _span("rbd_aio_write", "b", None, 2.0, 2.5),
        _span("objecter_op:write", "b1", "b", 2.2, 2.9),   # clipped
        _span("osd_op:write", "o", "a1", 0.15, 0.45),
        _span("ec_rmw_read", "r1", "o", 0.16, 0.17),
        _span("ec_rmw_read", "r2", "o", 0.2, 0.23),
    ]
    image = run.metric_reader("rbd_image_ms")(_Run(spans))
    rmw = run.metric_reader("ec_rmw_read_ms")(_Run(spans))
    assert image == pytest.approx(1e3 * (0.4 + 0.2) / 2)
    assert rmw == pytest.approx(1e3 * (0.01 + 0.03) / 2)
    other = [s for s in spans if s["name"].startswith("osd_op")]
    assert run.metric_reader("rbd_image_ms")(_Run(other)) is None
    assert run.metric_reader("ec_rmw_read_ms")(_Run(other)) is None


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(trace):
    cfg, mix = tiny_rbd()
    res = run_tiny(CELL, trace=trace, cfg=cfg, mix=mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    notes = res["notes"]
    assert notes["compiles_in_window"] == 0
    assert notes["ec_stripe_loop_calls"] == 0
    assert notes["ec_rmw_reads_per_op"] == 1.0
    assert notes["objecter_ops_in_flight_mean"] > 0
    if trace:
        assert {"rbd_image_ms", "ec_rmw_read_ms", "osd_op_ms",
                "client_msgr_ms", "ec_call_ms"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"client_MBps", "op_p95_ms",
                                       "setup_s"}


def test_a_program_without_a_data_pool_exits_before_any_cluster(
        monkeypatch):
    from benchmark.drivers import rbd_cluster
    from ceph_tpu import testing
    from ceph_tpu.rbd import RBD
    cfg, mix = tiny_rbd()

    def create(self, ioctx, name, size, order=22):
        raise AssertionError("never reached")

    monkeypatch.setattr(RBD, "create", create)
    monkeypatch.setattr(testing, "MiniCluster", None)
    with pytest.raises(SystemExit, match="no data_pool"):
        rbd_cluster.setup(cfg, mix, 5_000_000_001)


def ec_shards_unchanged():
    """The EC shards acknowledge their sub-writes and store none of
    their data (faults.ec_state_unchanged drops the image's header
    too, so the image would never open)."""
    from ceph_tpu.osd.ec_backend import ECPGShard
    from ceph_tpu.store.objectstore import OP_WRITE

    def make(orig):
        def handle_sub_write(self, m):
            if m.txn is not None:
                m.txn.ops = [op for op in m.txn.ops if op[0] != OP_WRITE]
            return orig(self, m)
        return handle_sub_write
    return faults.patched(ECPGShard, "handle_sub_write", make)


@pytest.mark.parametrize("plant", [faults.ec_control,
                                   faults.ec_answer_altered,
                                   ec_shards_unchanged])
def test_broken_path_is_not_correct(plant):
    cfg, mix = tiny_rbd()
    with plant():
        res = run_tiny(CELL, seed=5_000_000_003, cfg=cfg, mix=mix)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
