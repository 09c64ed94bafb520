"""The clay cell on the CPU at a tiny size: its reference against the
golden corpus and the plugin, a sound run correct (traced and not,
no per-stripe host loop in the window), and a run with the timed path
broken underneath not correct."""
import json
import os

import numpy as np
import pytest

from benchmark import faults, run
from benchmark.ref import clay
from benchmark.tests.tiny import SPEC, run_tiny

CELL = "clay-k4m2d5.read-degraded-4m"
CORPUS = os.path.join(os.path.dirname(__file__), "..", "..", "tests",
                      "fixtures", "ec_corpus.json")


def tiny_clay() -> tuple[dict, dict]:
    c = run.Cell(SPEC, CELL)
    cfg, mix = dict(c.config), dict(c.mix)
    cfg["pg_num"] = 8
    mix.update(object_bytes=65536, objects=16, payloads=4, in_flight=4,
               readback_sample=4)
    return cfg, mix


def test_reference_matches_the_corpus():
    """The corpus was written by the program's own clay plugin: a
    regression pin, not an independent answer.  The reference, built
    from the construction alone, reproduces it."""
    with open(CORPUS) as f:
        corpus = json.load(f)
    obj = bytes.fromhex(corpus["object_hex"])
    entries = [e for e in corpus["entries"] if e["plugin"] == "clay"]
    assert {json.dumps(e["profile"], sort_keys=True) for e in entries} \
        >= {'{"k": "4", "m": "2"}'}
    for e in entries:
        p = e["profile"]
        k, m = int(p["k"]), int(p["m"])
        got = clay.shard_streams(obj, k, m, int(p.get("d", k + m - 1)),
                                 e["chunk_size"])
        assert {str(i): c.hex() for i, c in enumerate(got)} == e["chunks"]


def test_the_documented_profile_expands_as_the_configuration_says():
    cfg = run.Cell(SPEC, CELL).config
    q, t, nu, alpha = clay.params(cfg["k"], cfg["m"], cfg["d"])
    ex = cfg["expanded"]
    assert (q, t, nu, alpha) == (ex["q"], ex["t"], ex["nu"],
                                 ex["sub_chunks"])
    assert cfg["stripe_unit"] // alpha == ex["sub_chunk_bytes"]
    assert clay.generator(cfg["k"], cfg["m"], cfg["d"]).shape == (16, 32)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_correct(trace):
    cfg, mix = tiny_clay()
    res = run_tiny(CELL, trace=trace, cfg=cfg, mix=mix)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["notes"]["compiles_in_window"] == 0
    assert res["notes"]["ec_stripe_loop_calls"] == 0
    assert 0 < res["notes"]["decoded_share"] < 1
    if trace:
        assert {"ec_call_ms", "ec_stage_ms", "ec_device_ms",
                "ec_host_ms", "ec_transfer_ms"} <= set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"client_MBps", "op_p95_ms",
                                       "setup_s"}


def test_a_program_without_the_batched_path_exits_before_any_cluster(
        monkeypatch):
    """A program that serves clay only stripe by stripe (no batched
    API) ends the run at once, with a message and no cluster built."""
    from benchmark.drivers import clay_cluster
    from ceph_tpu.osd import ecutil
    from ceph_tpu import testing
    cfg, mix = tiny_clay()
    monkeypatch.setattr(ecutil, "batchable", lambda ec: False)
    monkeypatch.setattr(testing, "MiniCluster", None)
    with pytest.raises(SystemExit, match="no batched path"):
        clay_cluster.setup(cfg, mix, 5_000_000_001)


def clay_control():
    """Each encode writes the last coding chunk's planes as zeros."""
    from ceph_tpu.ec.plugins.clay import ErasureCodeClay

    def make(orig):
        def encode_batch(self, data):
            out = np.array(orig(self, data))
            out[..., -self.sub_chunk_no:, :] = 0
            return out
        return encode_batch
    return faults.patched(ErasureCodeClay, "encode_batch", make)


@pytest.mark.parametrize("plant", [clay_control, faults.ec_answer_altered,
                                   faults.ec_state_unchanged])
def test_broken_path_is_not_correct(plant):
    cfg, mix = tiny_clay()
    with plant():
        res = run_tiny(CELL, seed=5_000_000_003, cfg=cfg, mix=mix)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
