"""crc32c: the CPU instruction paths against the slice-by-8 table and
the pure-Python walk, the interpreter lock kept across native calls,
and HashInfo compatibility across implementations.

Vectors: RFC 3720 B.4 and src/test/common/test_crc32c.cc:18-45.
"""
import ctypes
import random
import sys
import threading

import numpy as np
import pytest

from ceph_tpu.common import crc32c as crcmod
from ceph_tpu.common.crc32c import crc32c, crc32c_table, _crc32c_py
from ceph_tpu.msg.messages import ECSubRead
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.ec_backend import ECPGShard, HINFO_ATTR
from ceph_tpu.osd.ecutil import HashInfo
from ceph_tpu.store import MemStore, ObjectId, Transaction


def _rand(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


def test_rfc3720_vector():
    assert crc32c(0xFFFFFFFF, b"123456789") ^ 0xFFFFFFFF == 0xE3069283


def test_implementation_is_named():
    assert crcmod.implementation() in ("sse42", "armv8", "table", "python")


@pytest.mark.parametrize("seed", [0, 1, 0x12345678, 0xFFFFFFFF])
def test_zero_length_returns_the_raw_seed(seed):
    assert crc32c(seed, b"") == seed
    assert crc32c_table(seed, b"") == seed


@pytest.mark.parametrize("align", range(8))
def test_every_length_to_4096_agrees_with_table_and_python(align):
    seed = random.Random(align).getrandbits(32)
    buf = _rand(4096 + 8, align)
    data = memoryview(buf)[align:align + 4096]
    # the pure-Python walk gives the crc of every prefix in one pass
    prefix = [seed]
    for b in data:
        prefix.append(_crc32c_py(prefix[-1], bytes([b])))
    for n in range(4097):
        chunk = data[:n]
        assert crc32c(seed, chunk) == prefix[n], n
        assert crc32c_table(seed, chunk) == prefix[n], n


def test_4mib_agrees_with_table_and_python():
    data = _rand(4 << 20, 7)
    want = _crc32c_py(0xFFFFFFFF, data)
    assert crc32c(0xFFFFFFFF, data) == want
    assert crc32c_table(0xFFFFFFFF, data) == want


@pytest.mark.parametrize("split", [0, 1, 7, 255, 767, 768, 769, 24575,
                                   24576, 24583, 4 << 20, 1 << 20])
def test_chained_calls_equal_one_call(split):
    data = _rand((4 << 20) + (3 << 19), split)
    one = crc32c(0xFFFFFFFF, data)
    assert crc32c(crc32c(0xFFFFFFFF, data[:split]), data[split:]) == one


# lengths at the edges of the three-stream blocks (3 x 256, 3 x 8192)
# and of the loops after them
@pytest.mark.parametrize("n", [767, 768, 769, 1031, 24575, 24576, 24577,
                               24576 + 768 + 7])
def test_stream_block_edges_agree_with_table(n):
    data = _rand(n + 3, n)
    for off in (0, 3):
        chunk = memoryview(data)[off:off + n]
        assert crc32c(0xFFFFFFFF, chunk) == crc32c_table(0xFFFFFFFF, chunk)


def test_native_calls_keep_the_interpreter_lock():
    lib = crcmod._load_native()
    if lib is None:
        pytest.skip("no native library")
    # PyDLL functions carry the flag; CDLL ones give the lock up
    assert lib.one._flags_ & ctypes._FUNCFLAG_PYTHONAPI


@pytest.mark.parametrize("kind", ["bytes", "bytearray", "memoryview",
                                  "ndarray", "ndarray_u32", "strided"])
def test_input_types(kind):
    raw = _rand(10000, 3)
    arr = np.frombuffer(raw, dtype=np.uint8)
    data, want = {
        "bytes": (raw, raw),
        "bytearray": (bytearray(raw), raw),
        "memoryview": (memoryview(raw)[1:], raw[1:]),
        "ndarray": (arr, raw),
        "ndarray_u32": (arr.view(np.uint32), raw),
        "strided": (arr[::3], arr[::3].tobytes()),
    }[kind]
    expect = crc32c_table(0xFFFFFFFF, want)
    assert crc32c(0xFFFFFFFF, data) == expect


def test_threads_get_the_single_threaded_answers():
    # more threads than cores, and a short switch interval, so the
    # threads' calls interleave
    sizes = (512, 4096, 77, 1 << 20, (4 << 20) - 1, 4 << 20, 512 << 10,
             3 << 18) * 2
    bufs = [_rand(n, i) for i, n in enumerate(sizes)]
    want = [crc32c_table(0xFFFFFFFF, b) for b in bufs]
    got = [None] * len(bufs)
    start = threading.Barrier(len(bufs))

    def run(i):
        start.wait()
        for _ in range(5):
            c = crc32c(0xFFFFFFFF, bufs[i])
            if c != want[i]:
                got[i] = c
                return
        got[i] = crc32c_table(0xFFFFFFFF, bufs[i])
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(bufs))]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert got == want


# -- HashInfo written under one implementation, gated under the other --

K, M = 4, 2


def _hashinfo(appends):
    hi = HashInfo(K + M)
    for shards in appends:
        hi.append(hi.get_total_chunk_size(), shards)
    return hi


@pytest.mark.parametrize("writer", ["table", "native"])
def test_hashinfo_verifies_across_implementations(writer, monkeypatch):
    appends = [{s: _rand(n, 10 * i + s) for s in range(K + M)}
               for i, n in enumerate((512, 512 << 10, 4096))]
    native = _hashinfo(appends)
    with monkeypatch.context() as mp:
        mp.setattr(ecutil, "crc32c", crc32c_table)
        table = _hashinfo(appends)
    # stored hashes do not depend on the implementation
    assert table.to_dict() == native.to_dict()
    stored = table if writer == "table" else native
    if writer == "native":
        # the reverse: the gate itself runs the table implementation
        monkeypatch.setattr(crcmod, "crc32c", crc32c_table)
    for s in range(K + M):
        store = MemStore()
        store.mkfs()
        store.mount()
        shard = ECPGShard("1.0", s, store, K, M)
        stream = b"".join(a[s] for a in appends)
        good, bad = ObjectId("good", shard=s), ObjectId("bad", shard=s)
        flipped = bytes([stream[0] ^ 1]) + stream[1:]
        store.queue_transaction(
            Transaction()
            .write(shard.cid, good, 0, stream)
            .setattrs(shard.cid, good, {HINFO_ATTR: stored.to_dict()})
            .write(shard.cid, bad, 0, flipped)
            .setattrs(shard.cid, bad, {HINFO_ATTR: stored.to_dict()}))
        reply = shard.handle_sub_read(ECSubRead(
            pgid="1.0", tid=1, shard=s,
            to_read=[("good", 0, 0), ("bad", 0, 0)]))
        assert reply.buffers_read["good"] == stream
        assert reply.errors == {"bad": "EIO"}
