"""The reduction from a device trace to busy, idle and the metrics read
from it, on a small trace recorded on the chip and on exact cases."""
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import run, trace

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_v5e_encode.json")


def test_union_and_gaps_exact():
    ev = {"device": {"0": [("a", 10, 20), ("b", 15, 30), ("a", 50, 60)]},
          "host": [("profiled_window", 0, 100), ("submit", 0, 12),
                   ("wait", 25, 90)]}
    s = trace.reduce(ev)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["device_ops"][0] == ["a", pytest.approx(20e-9)]
    # gaps: [0,10) submit, [30,50) wait, [60,100) wait
    assert s["idle_gaps"][0] == ["wait", pytest.approx(40e-9)]
    assert s["idle_gaps"][1] == ["wait", pytest.approx(20e-9)]
    assert s["idle_gaps"][2] == ["submit", pytest.approx(10e-9)]


def test_no_window_or_no_device_work_reads_nothing():
    assert trace.reduce({"device": {}, "host": []}) is None
    assert trace.reduce({"device": {"0": []},
                         "host": [("profiled_window", 0, 10)]}) is None


def test_recorded_chip_trace():
    with open(FIXTURE) as f:
        ev = json.load(f)
    s = trace.reduce(ev)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    times = [t for _, t in s["device_ops"]]
    assert times == sorted(times, reverse=True)
    assert {n for n, _ in s["idle_gaps"]} <= {"submit", "wait",
                                              "no benchmark span"}


def _run(**kw):
    base = dict(trace={"busy_s": 2e-3, "window_s": 1.0}, profiled={},
                peaks=run.peaks_for("TPU v5 lite"), spans=[])
    base.update(kw)
    return SimpleNamespace(**base)


def test_device_readers():
    r = _run(profiled={"hbm_bytes": 819e6, "passes": 2})
    assert run.metric_reader("ec_roofline")(r) == pytest.approx(50.0)
    assert run.metric_reader("device_idle_pct.ec")(r) == \
        pytest.approx(99.8)
    assert run.metric_reader("crush_device_ms")(r) == pytest.approx(1.0)
    silent = _run(trace=None)
    for name in ("ec_roofline", "device_idle_pct.ec", "crush_device_ms",
                 "device_idle_pct.placement"):
        assert run.metric_reader(name)(silent) is None


def test_span_readers():
    sp = [
        {"name": "objecter_op:write_full", "span_id": "c", "parent": None,
         "start": 0.0, "end": 1.0},
        {"name": "osd_op:write_full", "span_id": "o", "parent": "c",
         "start": 0.1, "end": 0.9},
        {"name": "ec_encode_kernel", "span_id": "e", "parent": "o",
         "start": 0.2, "end": 0.4},
        {"name": "ec_sub_write", "span_id": "w", "parent": "o",
         "start": 0.3, "end": 0.6},
        {"name": "ec_decode_kernel", "span_id": "d", "parent": "o2",
         "start": 0.0, "end": 0.2},
        {"name": "stage", "span_id": "s", "parent": "d",
         "start": 0.0, "end": 0.05},
    ]
    r = _run(spans=sp)
    assert run.metric_reader("client_msgr_ms")(r) == pytest.approx(200.0)
    assert run.metric_reader("osd_op_ms")(r) == pytest.approx(400.0)
    assert run.metric_reader("ec_call_ms")(r) == pytest.approx(200.0)
    assert run.metric_reader("ec_stage_ms")(r) == pytest.approx(50.0)


def test_op_names_drop_shapes():
    assert trace.op_name("%gf_matmul_pallas_grouped.1 = u8[32,16,4096]"
                         " custom-call(s8[128,256] %b)") == \
        "gf_matmul_pallas_grouped.1"
