"""The harness's contract: no result off a TPU, peaks by device kind,
and cells, mixes and metrics found by name from files alone."""
import json
import os
import shutil

import pytest

from benchmark import run
from benchmark.tests.tiny import SPEC, tiny


def test_exits_without_result_off_tpu(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "ec-k8m4.write-4k", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_unknown_device_kind_is_an_error():
    assert run.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        run.peaks_for("cpu")


def test_every_named_file_exists():
    for c in SPEC["configs"]:
        cfg = run.load_json(run.ROOT, c["file"])
        assert os.path.exists(os.path.join(
            run.HERE, "drivers", cfg["driver"] + ".py"))
    for w in SPEC["workloads"]:
        assert os.path.exists(os.path.join(
            run.HERE, "mixes", w["traffic"] + ".json"))
    for m in SPEC["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


def test_new_mix_and_metric_from_files_alone(tmp_path, monkeypatch):
    """A later PR adds a cell (a mix file) and a per-layer metric (a
    reader file) plus their entries, and edits nothing that exists."""
    root = tmp_path / "checkout"
    shutil.copytree(run.HERE, root / "benchmark")
    spec = json.loads(json.dumps(SPEC))
    mix = dict(run.load_json(run.HERE, "mixes", "rados-write-4k.json"),
               object_bytes=16384, why="16 KiB writes")
    (root / "benchmark" / "mixes" / "rados-write-16k.json").write_text(
        json.dumps(mix))
    (root / "benchmark" / "metrics" / "sub_write_ms.py").write_text(
        "def read(run):\n"
        "    v = [s['end'] - s['start'] for s in run.spans\n"
        "         if s['name'] == 'ec_sub_write']\n"
        "    return 1e3 * sum(v) / len(v) if v else None\n")
    spec["workloads"].append({
        "name": "ec-k8m4.write-16k", "config": "ec-k8m4-13osd",
        "traffic": "rados-write-16k", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if "workloads" in m and "ec-k8m4.write-4k" in m["workloads"]:
            m["workloads"].append("ec-k8m4.write-16k")
    spec["per_layer"].append({
        "name": "sub_write_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "OSD primary",
        "moves": "op_p95_ms", "workloads": ["ec-k8m4.write-16k"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setattr(run, "ROOT", str(root))
    monkeypatch.setattr(run, "HERE", str(root / "benchmark"))
    spec = run.load_json(run.ROOT, "BENCHMARK.json")
    cell = run.Cell(spec, "ec-k8m4.write-16k")
    assert cell.mix["object_bytes"] == 16384
    cfg, _ = tiny("ec-k8m4.write-4k")
    tmix = dict(cell.mix, objects=16, payloads=4, in_flight=4,
                readback_sample=4)
    res = run.run_cell(spec, "ec-k8m4.write-16k", 5_000_000_007, 1.0,
                       True, require_tpu=False, config=cfg, mix=tmix)
    assert res["correct"], res["checks"]
    assert res["metrics"]["sub_write_ms"]["value"] > 0
