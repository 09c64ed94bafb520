"""Reed-Solomon encode over GF(2^8), written from ISA-L's definition.

Field polynomial x^8+x^4+x^3+x^2+1 (0x11d), generator 2; the coding
matrix is ISA-L's gf_gen_rs_matrix (erasure_code/ec_base.c): row i >= k
holds gen^0 .. gen^(k-1) with gen = 2^(i-k).  This is the `tpu` and
`isa` plugins' default technique, reed_sol_van."""
from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.cache
def mul_table() -> np.ndarray:
    """256 x 256 products, by shift-and-add (no log tables)."""
    t = np.zeros((256, 256), dtype=np.uint8)
    b = np.arange(256, dtype=np.int32)
    for a in range(256):
        acc = np.zeros(256, dtype=np.int32)
        x, y = a, b.copy()
        while x:
            if x & 1:
                acc ^= y
            y = y << 1
            y = np.where(y & 0x100, y ^ POLY, y)
            x >>= 1
        t[a] = acc
    return t


def rs_matrix(k: int, m: int) -> np.ndarray:
    """(m, k) coding rows of gf_gen_rs_matrix."""
    mul = mul_table()
    out = np.zeros((m, k), dtype=np.uint8)
    gen = 1
    for i in range(m):
        p = 1
        for j in range(k):
            out[i, j] = p
            p = int(mul[p, gen])
        gen = int(mul[gen, 2])
    return out


def encode(data: np.ndarray, m: int) -> np.ndarray:
    """(S, k, C) data chunks -> (S, m, C) parity chunks."""
    k = data.shape[-2]
    mat = rs_matrix(k, m)
    mul = mul_table()
    out = np.zeros(data.shape[:-2] + (m, data.shape[-1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            out[..., i, :] ^= mul[mat[i, j]][data[..., j, :]]
    return out


def shard_streams(payload: bytes, k: int, m: int,
                  chunk: int) -> list[bytes]:
    """Each of the k+m shards' chunk stream for one object written
    whole: the payload zero-padded to whole stripes of k chunks, shard
    s holding chunk s of every stripe in order (ECUtil's layout)."""
    width = k * chunk
    stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(stripes, k, chunk)
    parity = encode(data, m)
    return ([data[:, s, :].tobytes() for s in range(k)]
            + [parity[:, i, :].tobytes() for i in range(m)])
