"""Clay code (Ceph's clay plugin), written from its construction.

Vajha et al., "Clay Codes: Moulding MDS Codes to Yield an MSR Code"
(FAST 2018), with Ceph's node and plane indexing
(src/erasure-code/clay/ErasureCodeClay.cc):

- q = d - k + 1; nu zero-filled nodes pad k + m to a multiple of q;
  t = (k + m + nu) / q; every chunk is alpha = q^t sub-chunk planes,
  plane z of a chunk being its bytes [z * chunk / alpha, ...).
- Node y * q + x, for x < q and y < t: data chunk i is node i, the nu
  padding nodes follow, then coding chunk j is node k + nu + j.
- Plane z has the base-q digits z_y (z_0 the most significant).  At
  plane z, node (x, y) is unpaired where z_y == x: its uncoupled
  symbol U equals its coupled symbol C (the stored byte).  Otherwise
  it pairs with node (z_y, y) at the plane z' whose digit y is x; for
  the node of the larger x, (C, C', U, U') is a codeword of the (2, 2)
  pairwise code, whose coding rows give (U, U') from (C, C').
- In every plane the uncoupled symbols of all nodes form a codeword of
  the scalar MDS code (k + nu data, m coding).

Both codes are jerasure's reed_sol_van at w=8 (benchmark/ref/lrc.py),
over benchmark/ref/gf256's field.  The generator, the coding planes as
a GF(2^8)-linear map of the data planes, is solved from these
relations by plain Gaussian elimination, then applied stripe by
stripe.
"""
from __future__ import annotations

import functools

import numpy as np

from benchmark.ref.gf256 import mul_table
from benchmark.ref.lrc import reed_sol_van


def _inv(a: int) -> int:
    return int(np.nonzero(mul_table()[a] == 1)[0][0])


def _matmul(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """GF(2^8) product of two byte matrices."""
    mul = mul_table()
    out = np.zeros((a.shape[0], b.shape[1]), dtype=np.uint8)
    for i in range(a.shape[0]):
        for j in np.nonzero(a[i])[0]:
            out[i] ^= mul[a[i, j]][b[j]]
    return out


def _solve(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """X with a X = b over GF(2^8), a square, by Gauss-Jordan."""
    mul = mul_table()
    a, b = a.copy(), b.copy()
    n = a.shape[0]
    for col in range(n):
        piv = next(r for r in range(col, n) if a[r, col])
        a[[col, piv]], b[[col, piv]] = a[[piv, col]], b[[piv, col]]
        scale = _inv(int(a[col, col]))
        a[col], b[col] = mul[scale][a[col]], mul[scale][b[col]]
        for r in range(n):
            f = int(a[r, col])
            if r != col and f:
                a[r] ^= mul[f][a[col]]
                b[r] ^= mul[f][b[col]]
    return b


def params(k: int, m: int, d: int) -> tuple[int, int, int, int]:
    """(q, t, nu, alpha) of a clay profile."""
    q = d - k + 1
    nu = (q - (k + m) % q) % q
    t = (k + m + nu) // q
    return q, t, nu, q ** t


@functools.cache
def generator(k: int, m: int, d: int) -> np.ndarray:
    """(m * alpha, k * alpha) coding planes from data planes: row
    j * alpha + z is plane z of coding chunk j, column i * alpha + z
    plane z of data chunk i."""
    q, t, nu, alpha = params(k, m, d)
    nodes = q * t
    mds = reed_sol_van(k + nu, m)
    pft = reed_sol_van(2, 2)

    def sym(node: int, z: int) -> int:
        return node * alpha + z

    # U = T C, over every (node, plane) symbol
    tmat = np.zeros((nodes * alpha, nodes * alpha), dtype=np.uint8)
    for node in range(nodes):
        x, y = node % q, node // q
        for z in range(alpha):
            zy = z // q ** (t - 1 - y) % q
            if zy == x:
                tmat[sym(node, z), sym(node, z)] = 1
                continue
            mate = y * q + zy
            zm = z + (x - zy) * q ** (t - 1 - y)
            # the pair's codeword leads with the node of the larger x
            row = 0 if x > zy else 1
            cols = (sym(node, z), sym(mate, zm)) if x > zy \
                else (sym(mate, zm), sym(node, z))
            for c in range(2):
                tmat[sym(node, z), cols[c]] = pft[row, c]
    # H U = 0: in each plane, MDS coding rows plus the coding nodes
    hmat = np.zeros((m * alpha, nodes * alpha), dtype=np.uint8)
    for z in range(alpha):
        for r in range(m):
            for j in range(k + nu):
                hmat[r * alpha + z, sym(j, z)] = mds[r, j]
            hmat[r * alpha + z, sym(k + nu + r, z)] = 1
    a = _matmul(hmat, tmat)
    data = [sym(i, z) for i in range(k) for z in range(alpha)]
    coding = [sym(k + nu + j, z) for j in range(m) for z in range(alpha)]
    # the padding nodes hold zeros; in characteristic 2, A_c C_c = A_d C_d
    return _solve(a[:, coding], a[:, data])


def encode(data: np.ndarray, m: int, d: int) -> np.ndarray:
    """(S, k, C) data chunks -> (S, m, C) coding chunks."""
    mul = mul_table()
    s, k, c = data.shape
    alpha = params(k, m, d)[3]
    gen = generator(k, m, d)
    planes = data.reshape(s, k * alpha, c // alpha)
    out = np.zeros((s, m * alpha, c // alpha), dtype=np.uint8)
    for r in range(m * alpha):
        for j in np.nonzero(gen[r])[0]:
            out[:, r] ^= mul[gen[r, j]][planes[:, j]]
    return out.reshape(s, m, c)


def shard_streams(payload: bytes, k: int, m: int, d: int,
                  chunk: int) -> list[bytes]:
    """Each shard's chunk stream for one object written whole: the
    payload zero-padded to whole stripes of k chunks (ECUtil's layout),
    data shards then coding shards."""
    width = k * chunk
    stripes = max(1, -(-len(payload) // width))
    buf = np.zeros(stripes * width, dtype=np.uint8)
    buf[:len(payload)] = np.frombuffer(payload, dtype=np.uint8)
    data = buf.reshape(stripes, k, chunk)
    coding = encode(data, m, d)
    return [data[:, i].tobytes() for i in range(k)] + \
        [coding[:, j].tobytes() for j in range(m)]
