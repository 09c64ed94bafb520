"""Each cell end to end on the CPU at a tiny size, through run_cell."""
import pytest

from benchmark.tests.tiny import SPEC, run_tiny

CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    res = run_tiny(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    names = set(res["metrics"])
    want = {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert names == want
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reads_spans(cell):
    """On the CPU there is no device plane, so only span metrics
    appear; none is ever reported as 0."""
    res = run_tiny(cell, trace=True)
    assert res["correct"], res["checks"]
    for name, v in res["metrics"].items():
        assert v["value"] != 0, name
    assert "busy_s" not in res["device"]
    if cell.startswith("ec-k8m4"):
        assert {"client_msgr_ms", "osd_op_ms", "ec_call_ms"} <= \
            set(res["metrics"])
