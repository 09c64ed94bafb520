"""Scalar CRUSH placement for osdmaptool's --createsimple map, written
from upstream src/crush/{hash.c,mapper.c} and OSDMap::pg_to_up_acting.

The map is rebuilt here from the configuration alone: one straw2 host
bucket per `osds_per_host` OSDs (ids -1, -2, ...), one straw2 root over
the hosts (the next id), every OSD of CRUSH weight 1.0, the rule
`take root; chooseleaf firstn 0 type host; emit`, jewel tunables, a
replicated pool with HASHPSPOOL.  The one input the map takes from a
run is the 16.16 reweight vector (which OSDs are out).
"""
from __future__ import annotations

import json
import os

import numpy as np

M32 = 0xFFFFFFFF
SEED = 1315423911
NONE = 0x7FFFFFFF

_TABLES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "crush_ln.json")


def _mix(a, b, c):
    """crush_hashmix on uint64 arrays holding 32-bit values."""
    a = (a - b - c) & M32; a ^= c >> 13
    b = (b - c - a) & M32; b ^= (a << 8) & M32
    c = (c - a - b) & M32; c ^= b >> 13
    a = (a - b - c) & M32; a ^= c >> 12
    b = (b - c - a) & M32; b ^= (a << 16) & M32
    c = (c - a - b) & M32; c ^= b >> 5
    a = (a - b - c) & M32; a ^= c >> 3
    b = (b - c - a) & M32; b ^= (a << 10) & M32
    c = (c - a - b) & M32; c ^= b >> 15
    return a, b, c


def _u32(v):
    return np.atleast_1d(np.asarray(v, dtype=np.int64)).astype(
        np.uint64) & np.uint64(M32)


def hash2(a, b):
    """crush_hash32_rjenkins1_2."""
    a, b = _u32(a), _u32(b)
    h = np.uint64(SEED) ^ a ^ b
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    x, a, h = _mix(x, a, h)
    b, y, h = _mix(b, y, h)
    return h


def hash3(a, b, c):
    """crush_hash32_rjenkins1_3."""
    a, b, c = np.broadcast_arrays(_u32(a), _u32(b), _u32(c))
    a, b, c = a.copy(), b.copy(), c.copy()
    h = np.uint64(SEED) ^ a ^ b ^ c
    x = np.full_like(h, 231232)
    y = np.full_like(h, 1232)
    a, b, h = _mix(a, b, h)
    c, x, h = _mix(c, x, h)
    y, a, h = _mix(y, a, h)
    b, x, h = _mix(b, x, h)
    y, c, h = _mix(y, c, h)
    return h


def crush_ln_table() -> np.ndarray:
    """crush_ln(u) - 2^48 for every 16-bit u (mapper.c crush_ln)."""
    with open(_TABLES) as f:
        t = json.load(f)
    rh_lh = np.array(t["RH_LH"], dtype=np.uint64)
    ll_tbl = np.array(t["LL"], dtype=np.uint64)
    x = np.arange(1 << 16, dtype=np.int64) + 1
    # bits = clz32(x & 0x1ffff) - 16 where x & 0x18000 == 0
    nbits = np.zeros_like(x)
    v = x & 0x1FFFF
    for sh in range(16, -1, -1):
        nbits = np.where((nbits == 0) & (v >> sh != 0), sh + 1, nbits)
    bits = 32 - nbits - 16
    small = (x & 0x18000) == 0
    x = np.where(small, x << np.where(small, bits, 0), x)
    iexpon = np.where(small, 15 - bits, 15)
    index1 = (x >> 8) << 1
    rh = rh_lh[index1 - 256]
    lh = rh_lh[index1 + 1 - 256]
    xl64 = (x.astype(np.uint64) * rh) >> np.uint64(48)
    ll = ll_tbl[(xl64 & np.uint64(0xFF)).astype(np.int64)]
    res = iexpon.astype(np.uint64) << np.uint64(44)
    res = res + ((lh + ll) >> np.uint64(4))
    return res.astype(np.int64) - 0x1000000000000


class SimpleMap:
    """The --createsimple hierarchy and its replicated pool."""

    def __init__(self, n_osd: int, osds_per_host: int, pg_num: int,
                 size: int, pool_id: int = 0, total_tries: int = 50):
        self.n_osd = n_osd
        self.size = size
        self.pg_num = pg_num
        self.pool_id = pool_id
        self.tries = total_tries + 1
        self.hosts = [np.arange(b, min(b + osds_per_host, n_osd))
                      for b in range(0, n_osd, osds_per_host)]
        self.host_ids = -1 - np.arange(len(self.hosts))
        self.host_w = np.array([len(h) * 0x10000 for h in self.hosts],
                               dtype=np.int64)
        self.root = -1 - len(self.hosts)
        self.mask = (1 << max(0, (pg_num - 1).bit_length())) - 1
        self.ln = crush_ln_table()

    def pps(self, ps: int) -> int:
        """raw_pg_to_pps: ceph_stable_mod, then the pool-mixed hash."""
        folded = ps & self.mask
        if folded >= self.pg_num:
            folded = ps & (self.mask >> 1)
        return int(hash2(folded, self.pool_id)[0])

    def _straw2(self, items, ids, weights, x: int, r: int) -> int:
        u = hash3(x, ids, r) & np.uint64(0xFFFF)
        ln = self.ln[u.astype(np.int64)]
        w = np.asarray(weights, dtype=np.int64)
        safe = np.where(w > 0, w, 1)
        # div64_s64 truncates toward zero; ln <= 0 < w
        draw = np.where(w > 0, -((-ln) // safe), np.iinfo(np.int64).min)
        return int(items[int(np.argmax(draw))])

    def _is_out(self, reweight, item: int, x: int) -> bool:
        w = int(reweight[item])
        if w >= 0x10000:
            return False
        if w == 0:
            return True
        return int(hash2(x, item)[0] & np.uint64(0xFFFF)) >= w

    def _leaf(self, host: int, reweight, x: int, parent_r: int,
              out2: list) -> int | None:
        """The recursive choose inside one host: stable, numrep 1, one
        try (chooseleaf_descend_once)."""
        members = self.hosts[-1 - host]
        r = parent_r
        item = self._straw2(members, members,
                            np.full(len(members), 0x10000), x, r)
        if item in out2 or self._is_out(reweight, item, x):
            return None
        return item

    def map_pg(self, ps: int, reweight) -> list[int]:
        """up == acting for a pool with every OSD up and no temp or
        upmap entries: chooseleaf firstn over hosts (mapper.c
        crush_choose_firstn, jewel: vary_r 1, stable 1)."""
        x = self.pps(ps)
        out: list[int] = []        # hosts
        out2: list[int] = []       # leaves
        for rep in range(self.size):
            ftotal = 0
            while True:
                r = rep + ftotal
                host = self._straw2(self.host_ids, self.host_ids,
                                    self.host_w, x, r)
                reject = host in out
                if not reject:
                    leaf = self._leaf(host, reweight, x, r, out2)
                    reject = leaf is None
                if not reject:
                    out.append(host)
                    out2.append(leaf)
                    break
                ftotal += 1
                if ftotal >= self.tries:
                    break
        return out2
