"""Faults planted under the timed path, and each configuration's
control, to show that `correct` fails when the program is wrong.

Each is a context manager that patches the program for its duration.
The benchmark's own runs plant nothing; benchmark/control.py (on the
chip, at a cell's own size) and benchmark/tests/ (on the CPU, tiny)
run a cell under one of these and expect `correct` false.
"""
from __future__ import annotations

import contextlib

import numpy as np


@contextlib.contextmanager
def patched(obj, name: str, make):
    """Replace obj.name by make(original) for the duration."""
    orig = getattr(obj, name)
    setattr(obj, name, make(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


# ------------------------------------------------------------------ EC

def ec_control():
    """Breaks the guarantee that every live shard holds the code's
    bytes: the last parity chunk is written as zeros (m=4 stored as
    m=3, the shortcut a later PR might take to write less)."""
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu

    def make(orig):
        def encode_batch(self, data):
            out = np.array(orig(self, data))
            out[..., -1, :] = 0
            return out
        return encode_batch
    return patched(ErasureCodeTpu, "encode_batch", make)


def ec_answer_altered():
    """One byte of every encode's parity and every decode's output
    flipped where it is produced."""
    from ceph_tpu.osd import ecutil

    stack = contextlib.ExitStack()

    def make_enc(orig):
        def encode(sinfo, ec, data, want=None):
            out = orig(sinfo, ec, data, want)
            last = max(out)
            b = bytearray(out[last])
            b[0] ^= 0xFF
            out[last] = bytes(b)
            return out
        return encode

    def make_dec(orig):
        def decode_concat(sinfo, ec, to_decode, timings=None):
            out = bytearray(orig(sinfo, ec, to_decode, timings=timings))
            if len(to_decode) and len(out) and \
                    sorted(to_decode)[:ec.get_data_chunk_count()] != \
                    list(range(ec.get_data_chunk_count())):
                out[0] ^= 0xFF          # a read that decoded
            return bytes(out)
        return decode_concat
    stack.enter_context(patched(ecutil, "encode", make_enc))
    stack.enter_context(patched(ecutil, "decode_concat", make_dec))
    return stack


def ec_state_unchanged():
    """The OSDs acknowledge writes and store nothing of their data."""
    from ceph_tpu.store.memstore import MemStore
    from ceph_tpu.store.objectstore import OP_WRITE

    def make(orig):
        def queue_transaction(self, txn):
            txn.ops = [op for op in txn.ops if op[0] != OP_WRITE]
            return orig(self, txn)
        return queue_transaction
    return patched(MemStore, "queue_transaction", make)


def ec_half_batch():
    """Half of each encode's stripes left out (their parity zero; for a
    one-stripe encode, half of its bytes)."""
    from ceph_tpu.ec.plugins.tpu import ErasureCodeTpu

    def make(orig):
        def encode_batch(self, data):
            out = np.array(orig(self, data))
            if out.ndim == 3 and out.shape[0] > 1:
                out[out.shape[0] // 2:] = 0
            else:
                out[..., out.shape[-1] // 2:] = 0
            return out
        return encode_batch
    return patched(ErasureCodeTpu, "encode_batch", make)


# ----------------------------------------------------------- placement

def placement_control():
    """Breaks the guarantee that tables follow the current epoch: the
    mapping ignores reweights (every OSD taken as in), the shortcut of
    an update that skips the map change it was called for."""
    from ceph_tpu.osd.mapping import OSDMapMapping

    def make(orig):
        def _map_pool(self, osdmap, pool_id):
            saved = list(osdmap.osd_weight)
            osdmap.osd_weight = [0x10000] * len(saved)
            try:
                return orig(self, osdmap, pool_id)
            finally:
                osdmap.osd_weight = saved
        return _map_pool
    return patched(OSDMapMapping, "_map_pool", make)


def placement_answer_altered():
    """One OSD id of each pass's table changed where it is produced."""
    from ceph_tpu.osd.mapping import OSDMapMapping

    def make(orig):
        def _map_pool(self, osdmap, pool_id):
            pm = orig(self, osdmap, pool_id)
            for t in (pm.up, pm.acting):
                t[::997, 0] = (t[::997, 0] + 1) % osdmap.max_osd
            return pm
        return _map_pool
    return patched(OSDMapMapping, "_map_pool", make)


def placement_state_unchanged():
    """update() returns with the previous epoch's tables."""
    from ceph_tpu.osd.mapping import OSDMapMapping

    def make(orig):
        def update(self, osdmap, pool_ids=None):
            if self.epoch < 0:
                return orig(self, osdmap, pool_ids)
        return update
    return patched(OSDMapMapping, "update", make)


def placement_half_batch():
    """Half of each dispatch's PGs left out (their rows empty)."""
    from ceph_tpu.crush.batch import CompiledCrushMap

    def make(orig):
        def map_batch(self, xs, weight, ruleno=0, result_max=None,
                      return_counts=False):
            res = orig(self, xs, weight, ruleno, result_max,
                       return_counts)
            n = len(xs) // 2
            if return_counts:
                r, c = np.array(res[0]), np.array(res[1])
                c[n:] = 0
                return r, c
            r = np.array(res)
            r[n:] = 0x7FFFFFFF
            return r
        return map_batch
    return patched(CompiledCrushMap, "map_batch", make)


CONTROLS = {"ec_cluster": ec_control, "placement": placement_control}

#: the faults each kind of cell can have (no cell spans chips, so
#: none has an exchange between chips to leave out)
FAULTS = {
    "ec_cluster": {"answer_altered": ec_answer_altered,
                   "state_unchanged": ec_state_unchanged,
                   "half_batch": ec_half_batch},
    "placement": {"answer_altered": placement_answer_altered,
                  "state_unchanged": placement_state_unchanged,
                  "half_batch": placement_half_batch},
}
