"""The benchmark's readers of queue-wait and EC-stage spans, on small
hand-built span sets: each reads what its definition says, and a
program that records none of its spans gives nothing."""
import os

import pytest

from benchmark import run as bench

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW = ["msgr_queue_ms", "subop_queue_ms", "ec_host_ms",
       "ec_transfer_ms", "ec_device_ms"]


class Run:
    def __init__(self, spans):
        self.spans = spans


def sp(name, sid, parent, start, end):
    return {"name": name, "span_id": sid, "parent": parent,
            "trace_id": "t", "start": start, "end": end}


def write_op(n, queued=True, staged=True):
    """One traced write: client leg, primary, encode, two sub-writes."""
    p = f"{n}."
    spans = [
        sp("objecter_op:write_full", p + "c", None, 0.0, 1.0),
        sp("osd_op:write_full", p + "o", p + "c", 0.1, 0.9),
        sp("ec_encode_kernel", p + "e", p + "o", 0.2, 0.4),
        sp("ec_sub_write", p + "w1", p + "o", 0.5, 0.6),
        sp("ec_sub_write", p + "w2", p + "o", 0.5, 0.7),
    ]
    if queued:
        spans += [
            sp("ms_queue:OSDOp", p + "q0", p + "c", 0.02, 0.1),
            sp("ms_queue:OSDOpReply", p + "q9", p + "c", 0.92, 1.0),
            sp("ms_queue:ECSubWrite", p + "q1", p + "o", 0.4, 0.5),
            sp("ms_queue:ECSubWrite", p + "q2", p + "o", 0.45, 0.5),
            sp("ms_queue:ECSubWriteReply", p + "q3", p + "o", 0.7, 0.8),
            # clipped to the op
            sp("ms_queue:ECSubWriteReply", p + "q4", p + "o", 0.85, 0.95),
        ]
    if staged:
        spans += [
            sp("stage", p + "s0", p + "e", 0.2, 0.22),
            sp("h2d", p + "s1", p + "e", 0.22, 0.25),
            sp("device", p + "s2", p + "e", 0.25, 0.3),
            sp("d2h", p + "s3", p + "e", 0.3, 0.34),
            sp("unstage", p + "s4", p + "e", 0.34, 0.4),
        ]
    return spans


def healthy_read(n):
    """A read with every data shard: its decode call only unstages."""
    p = f"r{n}."
    return [sp("ec_decode_kernel", p + "d", "x", 0.0, 0.1),
            sp("unstage", p + "u", p + "d", 0.0, 0.1)]


@pytest.mark.parametrize("name,want", [
    ("msgr_queue_ms", 1e3 * (0.08 + 0.08)),
    ("subop_queue_ms", 1e3 * (0.1 + 0.1 + 0.05)),
    ("ec_host_ms", 1e3 * (0.02 + 0.06)),
    ("ec_transfer_ms", 1e3 * (0.03 + 0.04)),
    ("ec_device_ms", 1e3 * 0.05),
])
def test_reader_reads_its_definition(name, want):
    spans = write_op(0) + write_op(1)
    assert bench.metric_reader(name)(Run(spans)) == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_nothing_without_its_spans(name):
    """The parent program's spans: no queue spans, no EC regions."""
    spans = write_op(0, queued=False, staged=False)
    assert bench.metric_reader(name)(Run(spans)) is None


def test_host_share_counts_every_call_device_ones_only_their_own():
    """A healthy read's decode counts in ec_host_ms; only calls that
    reached the device count in the transfer and device means."""
    spans = write_op(0) + healthy_read(0)
    host = bench.metric_reader("ec_host_ms")(Run(spans))
    assert host == pytest.approx(1e3 * (0.08 + 0.1) / 2)
    xfer = bench.metric_reader("ec_transfer_ms")(Run(spans))
    assert xfer == pytest.approx(1e3 * 0.07)


def test_queue_spans_leave_older_readers_intact():
    """client_msgr_ms still reads the single osd_op child; osd_op_ms
    now subtracts the queue waits under the op as well."""
    spans = write_op(0)
    client = bench.metric_reader("client_msgr_ms")(Run(spans))
    assert client == pytest.approx(1e3 * (1.0 - 0.8))
    own = bench.metric_reader("osd_op_ms")(Run(spans))
    queued = bench.metric_reader("subop_queue_ms")(Run(spans))
    # children: encode .2-.4, queues .4-.5, sub-writes .5-.7,
    # replies .7-.8 and .85-.9 (clipped)
    assert own == pytest.approx(1e3 * (0.8 - 0.65))
    assert queued <= 1e3 * 0.8


def test_every_new_metric_is_declared_for_the_ec_cells():
    spec = bench.load_json(ROOT, "BENCHMARK.json")
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    ec = [w["name"] for w in spec["workloads"]
          if w["config"] == "ec-k8m4-13osd"]
    for name in NEW:
        assert per_layer[name]["workloads"] == ec
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
