"""chip_smoke.py: its phases at a tiny size on the CPU, and its refusal
to run at all without a TPU (so does bench.py)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chip_smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def last_line(capsys, phase):
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == phase
    assert line["platform"] == "cpu"
    return line


@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py"])
def test_refuses_to_run_without_a_tpu(script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, script], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_ec_phase(capsys):
    chip_smoke.phase_ec(np.random.default_rng(0), stripes=4, chunk=4096,
                        checked=4)
    line = last_line(capsys, "ec")
    assert line["byte_mismatches"] == 0 and line["stripes_checked"] == 4
    assert line["use_pallas"] is False


def test_placement_phase(capsys):
    chip_smoke.phase_placement(np.random.default_rng(0), n_osd=60,
                               pg_num=1000, chunk=256, sample=64)
    line = last_line(capsys, "placement")
    assert line["dispatches"] == 4
    assert line["placement_mismatches"] == 0 and line["pgs_checked"] == 64


def test_cluster_phase(capsys):
    chip_smoke.phase_cluster(np.random.default_rng(0), n_osd=13,
                             small=(3, 64 << 10), large=(1, 256 << 10))
    line = last_line(capsys, "cluster")
    assert line["objects"] == 4 and line["bytes"] == 448 << 10
    assert line["read_mismatches"] == 0
    assert line["degraded_read_mismatches"] == 0
    assert line["objects_on_killed_osd"] > 0 and line["decoders_built"] > 0


def test_mesh_phase(capsys):
    chip_smoke.phase_mesh(np.random.default_rng(0), n_devices=4,
                          stripes=4, chunk=4096)
    line = last_line(capsys, "mesh")
    assert line["output_devices"] == 4
    assert line["encode_equal"] and line["decode_equal"]


def test_fabric_cluster_phase(capsys):
    chip_smoke.phase_fabric_cluster(np.random.default_rng(0), n_devices=4,
                                    nbytes=256 << 10)
    line = last_line(capsys, "fabric_cluster")
    assert line["staged"] >= 1 and line["fetched"] >= 12
    assert line["read_ok"] and line["degraded_read_ok"]


def test_control_plane_never_starts_the_device_backend():
    """mon, mgr and mds code (and a mon validating a plugin=tpu EC
    profile) leave the JAX backend unstarted: on a chip host the chip
    belongs to the one process that runs plugin=tpu."""
    code = (
        "import ceph_tpu.mon.monitor, ceph_tpu.mgr, ceph_tpu.fs.mds\n"
        "import ceph_tpu.tools.daemon_main\n"
        "from ceph_tpu.ec import registry\n"
        "ec = registry.factory('tpu', {'k': '8', 'm': '4'})\n"
        "assert ec.get_chunk_size(1 << 20) == 131072\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge._backends, xla_bridge._backends\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
