"""clay pools on ecutil's one-dispatch device path.

Encode, decode and decode_concat of a clay profile over a byte matrix
code run as one GF(2^8) matmul each over (shard, plane) sub-chunks,
lowered from the plugin's own coupled-layer arithmetic; their bytes
must equal the per-stripe host path's and the plain reference's
(benchmark/ref/clay.py), for every signature of one and two erasures.
A single lost chunk with d survivors decodes from the d helpers' repair
planes.  The fused kernel takes clay's narrow plane lanes itself, and
keeps its layout for every shape the RS and lrc cells dispatch.
"""
import itertools
import json
import os
import sys
import threading

import numpy as np
import pytest

from benchmark.ref import clay as ref
from ceph_tpu.ec import registry, repairc
from ceph_tpu.ec.kernels import bitmatmul
from ceph_tpu.osd import ecutil

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
PROFILE = {"k": "4", "m": "2", "d": "5"}
STRIPES = 3


def _code(profile=PROFILE):
    return registry.factory("clay", dict(profile))


def _host(profile=PROFILE):
    """The same profile with no batched API: ecutil's per-stripe path."""
    ec = _code(profile)
    ec.encode_batch = ec.decode_batch = None
    return ec


def _sinfo(ec) -> ecutil.StripeInfo:
    k = ec.get_data_chunk_count()
    return ecutil.StripeInfo(k, k * ec.get_chunk_size(k * 1024))


def _payload(sinfo, seed: int) -> bytes:
    return np.random.default_rng(seed).bytes(STRIPES * sinfo.stripe_width)


def _ref_shards(ec, data: bytes, cs: int) -> dict[int, bytes]:
    got = ref.shard_streams(data, ec.k, ec.m, ec.d, cs)
    return dict(enumerate(got))


@pytest.fixture
def kernel_calls(monkeypatch):
    """Device kernel (GFMatmul) calls: (bytes in, bytes out) of each."""
    calls = []
    orig = bitmatmul.GFMatmul.__call__

    def counted(self, data):
        out = orig(self, data)
        calls.append((data.nbytes, out.nbytes))
        return out
    monkeypatch.setattr(bitmatmul.GFMatmul, "__call__", counted)
    return calls


@pytest.mark.parametrize("profile", [
    PROFILE, {"k": "6", "m": "3", "d": "8"},
    {"k": "4", "m": "2", "scalar_mds": "isa"},
    {"k": "3", "m": "2", "d": "4"}])
def test_encode_matches_host_path_and_reference(profile, kernel_calls):
    ec = _code(profile)
    assert ecutil.batchable(ec) and not ecutil.batchable(_host(profile))
    sinfo = _sinfo(ec)
    data = _payload(sinfo, seed=21)
    got = ecutil.encode(sinfo, ec, data)
    assert kernel_calls == [(len(data), len(data) // ec.k * ec.m)]
    assert got == ecutil.encode(sinfo, _host(profile), data)
    if profile.get("scalar_mds", "jerasure") == "jerasure":
        assert got == _ref_shards(ec, data, sinfo.chunk_size)
    prog = ec.batch_program()[0]
    alpha = ec.get_sub_chunk_count()
    assert prog.matrix.shape == (ec.m * alpha, ec.k * alpha)


def test_clay_takes_the_batched_path_with_the_regions_of_stages(
        kernel_calls):
    """Encode and a degraded decode_concat each time the regions of
    STAGES, `program` only on the call that built its program."""
    ec = _code()
    sinfo = _sinfo(ec)
    data = _payload(sinfo, seed=22)
    for call in range(2):
        timings: dict = {}
        shards = ecutil.encode(sinfo, ec, data, timings=timings)
        assert ("program" in timings) == (call == 0)
        assert {"stage", "h2d", "device", "d2h", "unstage"} <= \
            set(timings) <= set(ecutil.STAGES) - {"kernel"}
    avail = {i: c for i, c in shards.items() if i != 1}
    timings = {}
    assert ecutil.decode_concat(sinfo, ec, avail, timings=timings) == data
    assert {"stage", "program", "h2d", "device", "d2h", "unstage"} == \
        set(timings)
    assert len(kernel_calls) == 3


def test_every_signature_of_up_to_two_erasures(kernel_calls):
    """decode and decode_concat of every erasure set of one and two
    shards, and of one shard with another missing besides: one dispatch
    per call, the bytes of the per-stripe path and of the reference.
    One lost chunk with d survivors stages its d helpers' repair planes
    (q^(t-1) of q^t each); any other signature k whole survivors."""
    ec, host = _code(), _host()
    n, k, alpha = ec.get_chunk_count(), ec.k, ec.get_sub_chunk_count()
    sinfo = _sinfo(ec)
    cs = sinfo.chunk_size
    plane = STRIPES * cs // alpha
    data = _payload(sinfo, seed=23)
    shards = ecutil.encode(sinfo, ec, data)
    assert shards == _ref_shards(ec, data, cs)
    cases = [(lost, lost) for r in (1, 2)
             for lost in itertools.combinations(range(n), r)]
    cases += [((a,), (a, b)) for a, b in itertools.permutations(range(n), 2)]
    for want, gone in cases:
        avail = {i: c for i, c in shards.items() if i not in gone}
        repair = len(want) == 1 and len(avail) >= ec.d
        extents = ecutil.decode_extents(ec, want, avail)
        assert ecutil.staged_planes(extents) == \
            (ec.d * alpha // ec.q if repair else k * alpha), (want, gone)
        del kernel_calls[:]
        got = ecutil.decode(sinfo, ec, avail, want=want)
        assert got == {i: shards[i] for i in want}, (want, gone)
        assert kernel_calls == [(ecutil.staged_planes(extents) * plane,
                                 len(want) * STRIPES * cs)]
        assert ecutil.decode(sinfo, host, avail, want=want) == got
        if set(gone) & set(range(k)):
            assert ecutil.decode_concat(sinfo, ec, avail) == data
            assert ecutil.decode_concat(sinfo, host, avail) == data


def test_one_lost_data_chunk_decodes_from_the_repair_plan(kernel_calls):
    """A degraded read that lost data chunk 0, with shards 1-5 alive:
    the program is recovery's d-helper repair plan, 8 planes rebuilt
    from 4 of each helper's 8, built once."""
    ec = _code()
    sinfo = _sinfo(ec)
    data = _payload(sinfo, seed=24)
    shards = ecutil.encode(sinfo, ec, data)
    avail = {i: c for i, c in shards.items() if i != 0}
    assert ecutil.decode_extents(ec, [0], avail) == \
        {h: [(0, 4)] for h in range(1, 6)}
    for call in range(2):
        built = sum(repairc.cache_of(ec).stats()["compiles"].values())
        assert ecutil.decode_concat(sinfo, ec, avail) == data
        assert sum(repairc.cache_of(ec).stats()["compiles"].values()) \
            - built == (call == 0)
    prog, built = ec.batch_program([1, 2, 3, 4, 5], [0])
    assert not built
    assert prog.plan == ec.repair_schedule({0}, {1, 2, 3, 4, 5})
    assert prog.matrix.shape == (8, 20)


def test_concurrent_misses_build_each_program_once():
    """Threads that miss on the same signatures at once: each program
    is built once, its probes undisturbed by another build's (the
    plugin's interpreted state is shared), and equals one built
    alone."""
    ec, alone = _code(), _code()
    sigs = [((), ())] + [(sorted(set(range(6)) - {lost}), [lost])
                         for lost in range(6)]
    want = [alone.batch_program(*sig)[0].matrix for sig in sigs]
    got: list = []

    def worker(j: int) -> None:
        for i in range(len(sigs)):
            i = (i + j) % len(sigs)
            got.append((i, ec.batch_program(*sigs[i])[0].matrix))
    threads = [threading.Thread(target=worker, args=(j,))
               for j in range(12)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert len(got) == 12 * len(sigs)
    assert all(np.array_equal(m, want[i]) for i, m in got)
    compiles = repairc.cache_of(ec).stats()["compiles"]
    assert len(compiles) == len(sigs) and set(compiles.values()) == {1}


@pytest.mark.parametrize("profile", [{"k": "4", "m": "2"},
                                     {"k": "6", "m": "3", "d": "8"}])
def test_corpus_entries_on_the_device_path(profile):
    """The golden corpus's object through ecutil's batched encode, the
    reference and a decode of every single erasure.  The corpus was
    written by this tree's own plugin, so it pins against regressions;
    the reference is the independent answer."""
    with open(os.path.join(FIXTURES, "ec_corpus.json")) as f:
        corpus = json.load(f)
    entry = next(e for e in corpus["entries"] if e["plugin"] == "clay"
                 and e["profile"] == profile)
    obj = bytes.fromhex(corpus["object_hex"])
    ec = _code(profile)
    cs = entry["chunk_size"]
    sinfo = ecutil.StripeInfo(ec.k, ec.k * cs)
    padded = obj + bytes(-len(obj) % sinfo.stripe_width)
    got = ecutil.encode(sinfo, ec, padded)
    assert {str(i): c.hex() for i, c in got.items()} == entry["chunks"]
    assert got == _ref_shards(ec, obj, cs)
    for lost in range(len(got)):
        avail = {i: c for i, c in got.items() if i != lost}
        assert ecutil.decode(sinfo, ec, avail, want=[lost])[lost] == \
            got[lost]
        assert ecutil.decode_concat(sinfo, ec, avail) == padded


@pytest.mark.parametrize("r,k,s", [(16, 32, 6), (8, 20, 4), (8, 20, 3),
                                   (16, 32, 1)])
def test_pallas_kernel_takes_clay_planes(r, k, s, monkeypatch):
    """Clay's shapes, 512-byte plane lanes: the encode's 32 input
    planes and a repair's 20, over several stripes, on the fused kernel
    (interpret mode), each stripe whole in one grid cell's block, and
    no XLA fallback; the bytes are the XLA path's."""
    rng = np.random.default_rng(r * 100 + k + s)
    mat = rng.integers(0, 256, (r, k), dtype=np.uint8)
    data = rng.integers(0, 256, (s, k, 512), dtype=np.uint8)
    want = np.asarray(bitmatmul.gf_matmul_xla(
        bitmatmul.companion_bitmatrix(mat.tobytes(), r, k), data))
    assert bitmatmul.pallas_layout(s, k, 512) == (1, 512, s)

    def refused(*_):
        raise AssertionError("XLA fallback taken")
    monkeypatch.setattr(bitmatmul, "gf_matmul_xla", refused)
    got = np.asarray(bitmatmul.gf_matmul_pallas(mat, data, interpret=True))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("s,k,n,layout", [
    # ec-k8m4: a 4 MiB object's 128 stripes of 8 x 4 KiB (write-4m's
    # encode, read-degraded-4m's decode and prefill), one 4 KiB write
    pytest.param(128, 8, 4096, (4, 2048, 1), id="k8-4m"),
    pytest.param(1, 8, 4096, (1, 2048, 1), id="k8-4k"),
    # lrc-k4m2l3: a 4 MiB object's 256 stripes, the 4-row encode and
    # global decode, the 3-row local decode
    pytest.param(256, 4, 4096, (4, 2048, 1), id="lrc-encode"),
    pytest.param(256, 3, 4096, (4, 2048, 1), id="lrc-local"),
    # clay k=4 m=2 d=5: 256 stripes of 32 and of 20 planes x 512
    pytest.param(256, 32, 512, (1, 512, 16), id="clay-encode"),
    pytest.param(256, 20, 512, (1, 512, 16), id="clay-repair"),
])
def test_kernel_layout_of_every_cell_shape(s, k, n, layout):
    assert bitmatmul.pallas_layout(s, k, n) == layout
