"""EC coding over a 2-D (stripe, shard) device mesh.

Layout: data (S, k, N) placed with PartitionSpec('stripe', 'shard',
None) — each device holds a slice of the stripe batch and a subset of
the k data chunks (the device-resident analogue of chunk shards living
on k different OSDs).  Coding runs as one `shard_map` step per batch:

  * each device lifts its local chunk subset to GF(2) bit-planes and
    multiplies by its column slice of the companion matrix (partial
    bit-counts, MXU work, no communication);
  * a `psum` over the 'shard' axis XORs the partials (mod-2 of the
    summed counts) — this collective IS the reference's per-shard
    write fan-out (ref: src/osd/ECBackend.cc:2037-2070), riding ICI
    instead of the messenger;
  * the packed parity lands stripe-sharded, replicated over 'shard',
    ready for per-device placement.

Decode is the same structure with the erasure-specific decode matrix
over survivor chunks (ref: ECBackend.cc:1590 min-avail shard read +
reconstruct).
"""
from __future__ import annotations

import numpy as np

from ..ec import gf
from ..ec.matrix_code import make_decode_matrix


def make_mesh(n_devices: int | None = None, shard_ways: int | None = None,
              k: int = 8):
    """(stripe, shard) mesh over the first n devices; shard_ways must
    divide both the device count and k (chunk subsets stay equal)."""
    import jax
    devs = jax.devices()
    n = n_devices if n_devices is not None else len(devs)
    if n <= 0:
        raise ValueError(f"n_devices must be positive, got {n}")
    if n > len(devs):
        raise ValueError(f"{n} devices requested, {len(devs)} present")
    if shard_ways is None:
        shard_ways = next(c for c in (4, 2, 1)
                          if n % c == 0 and k % c == 0)
    if n % shard_ways or k % shard_ways:
        raise ValueError(
            f"shard_ways={shard_ways} must divide n={n} and k={k}")
    from jax.sharding import Mesh
    return Mesh(np.asarray(devs[:n]).reshape(n // shard_ways,
                                             shard_ways),
                ("stripe", "shard"))


class MeshECCoder:
    """Sharded encode/decode for one (k, m) code on one mesh."""

    def __init__(self, k: int, m: int, mesh,
                 encode_matrix: np.ndarray | None = None):
        import jax.numpy as jnp
        self.k = k
        self.m = m
        self.mesh = mesh
        self.shard_ways = mesh.devices.shape[1]
        if k % self.shard_ways:
            raise ValueError("k must divide over the shard axis")
        if encode_matrix is None:
            encode_matrix = gf.isa_rs_matrix(k, m)
        self.encode_matrix = np.ascontiguousarray(encode_matrix,
                                                  dtype=np.uint8)
        self._enc_bits = jnp.asarray(gf.expand_to_bitmatrix(
            self.encode_matrix[k:]).astype(np.int8))      # (8m, 8k)
        # one jitted shard_map step serves every matrix: jit re-traces
        # per argument shape and caches internally, so all erasure
        # patterns of one geometry share a single compilation
        self._step = None
        self._dec_bits: dict[str, object] = {}

    # ------------------------------------------------------- placement
    def shard_data(self, data_np: np.ndarray):
        """Host (S, k, N) -> device array sharded (stripe, shard)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(
            data_np, NamedSharding(self.mesh, P("stripe", "shard", None)))

    # ---------------------------------------------------------- encode
    def _coder(self):
        if self._step is None:
            self._step = self._build_coder()
        return self._step

    def _build_coder(self):
        """shard_map step: local partial bit-counts + psum('shard')."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def local_step(B_local, data_local):
            # data_local: (S/stripe_ways, k/shard_ways, N)
            s, kl, n = data_local.shape
            shifts = jnp.arange(8, dtype=jnp.uint8)
            bits = ((data_local[:, :, None, :] >>
                     shifts[None, None, :, None]) & 1)
            bits = bits.reshape(s, 8 * kl, n).astype(jnp.int8)
            partial = jnp.einsum("ij,sjn->sin", B_local, bits,
                                 preferred_element_type=jnp.int32)
            total = jax.lax.psum(partial, "shard")   # XOR via mod-2
            bits_out = total & 1                     # (s, 8r, n)
            r = bits_out.shape[1] // 8
            weights = (1 << jnp.arange(8, dtype=jnp.int32))
            planes = bits_out.reshape(s, r, 8, n) * \
                weights[None, None, :, None]
            return planes.sum(axis=2).astype(jnp.uint8)

        return jax.jit(shard_map(
            local_step, mesh=self.mesh,
            in_specs=(P(None, "shard"), P("stripe", "shard", None)),
            out_specs=P("stripe", None, None)))

    def encode(self, data):
        """data (S, k, N) sharded (stripe, shard) -> parity (S, m, N)
        sharded (stripe), one collective step."""
        return self._coder()(self._enc_bits, data)

    # ---------------------------------------------------------- decode
    def decode(self, decode_index: list[int], erasures: list[int],
               survivors):
        """survivors (S, k, N) — chunks `decode_index` in order,
        sharded (stripe, shard) -> reconstructed erasures (S, e, N)."""
        import jax.numpy as jnp
        sig = f"{tuple(decode_index)}-{tuple(erasures)}"
        bits = self._dec_bits.get(sig)
        if bits is None:
            dmat = make_decode_matrix(self.encode_matrix, self.k,
                                      list(decode_index), list(erasures))
            bits = jnp.asarray(
                gf.expand_to_bitmatrix(dmat).astype(np.int8))
            self._dec_bits[sig] = bits
        return self._coder()(bits, survivors)

    # ------------------------------------------------------ validation
    def check_parity(self, data_np: np.ndarray, parity) -> bool:
        """Full-batch oracle comparison (per-stripe, so stripe-axis
        placement bugs can't hide behind a correct stripe 0)."""
        got = np.asarray(parity)
        for i in range(data_np.shape[0]):
            want = gf.gf_matmul_bytes(self.encode_matrix[self.k:],
                                      data_np[i])
            if not np.array_equal(got[i], want):
                return False
        return True
