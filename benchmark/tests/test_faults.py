"""A run with the timed path broken underneath comes out not correct:
the control of each configuration and each fault a cell can have."""
import pytest

from benchmark import control
from benchmark.faults import FAULTS
from benchmark.tests.tiny import SPEC, tiny

CASES = [(cell, fault)
         for cell in ("ec-k8m4.write-4m", "ec-k8m4.write-4k",
                      "ec-k8m4.read-degraded-4m", "placement-10k.remap")
         for fault in [None] + sorted(FAULTS[
             "placement" if cell.startswith("placement") else
             "ec_cluster"])]


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_path_is_not_correct(cell, fault):
    cfg, mix = tiny(cell)
    res = control.run_under(SPEC, cell, 5_000_000_003, 1.0, fault,
                            require_tpu=False, config=cfg, mix=mix)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
