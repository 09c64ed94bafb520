"""Maintained throughput probe for the GF(2^8) MXU kernel formulations.

Run on the chip (`python scripts/perf_probe.py` through the chip tool);
it prints the JAX backend it ran on.  Each timing chains R distinct
encodes inside one jit and reads back a single scalar, so one host sync
covers R device dispatches.

Compares, at k=8 m=4, 1 MiB objects:
  - xla          : per-stripe batched (8m x 8k) matmul (baseline)
  - xla-g<G>     : block-diagonal grouped (8mG x 8kG) dense-tile matmul
  - pallas-g<G>-t<TN>: fused grouped Pallas kernel, bit-planes in VMEM
"""
import functools
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ceph_tpu.common.compile_cache import use_compile_cache
from ceph_tpu.ec import gf
from ceph_tpu.ec.kernels import bitmatmul as bm

use_compile_cache()

K, M = 8, 4
CHUNK = 128 * 1024
STRIPES = 256
REPS = 50

rng = np.random.default_rng(0)
mat = gf.isa_rs_matrix(K, M)[K:]
data = jnp.asarray(
    rng.integers(0, 256, (STRIPES, K, CHUNK), dtype=np.uint8))
want = gf.gf_matmul_bytes(mat, np.asarray(data[0]))


def measure(step, label):
    """step: (data, i) -> parity; chained over unique reps."""
    @jax.jit
    def chained(d):
        def body(c, i):
            out = step(d ^ i, i)
            return c + jnp.sum(out, dtype=jnp.int32), None
        acc, _ = lax.scan(body, jnp.int32(0),
                          jnp.arange(REPS, dtype=jnp.uint8))
        return acc

    jax.block_until_ready(chained(data))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(chained(data))
    dt = (time.perf_counter() - t0) / REPS
    gbs = STRIPES * K * CHUNK / dt / 1e9
    print(f"{label:24s} {dt * 1e3:7.2f} ms   {gbs:7.1f} GB/s data-in")
    return gbs


def check(fn, label):
    out = np.asarray(fn(data)[0])
    ok = np.array_equal(out, want)
    if not ok:
        print(f"{label}: PARITY MISMATCH vs oracle")
    return ok


def main():
    print(f"backend={jax.default_backend()} stripes={STRIPES} "
          f"chunk={CHUNK} reps={REPS}")
    B = jnp.asarray(bm.companion_bitmatrix(
        np.ascontiguousarray(mat).tobytes(), M, K))
    results = {}

    assert check(lambda d: bm.gf_matmul_xla(B, d), "xla")
    results["xla"] = measure(lambda d, i: bm.gf_matmul_xla(B, d), "xla")

    for g in (4, 8, 16):
        if STRIPES % g:
            continue
        Bg = jnp.asarray(bm.grouped_bitmatrix(
            np.ascontiguousarray(mat).tobytes(), M, K, g))
        Bgp = jnp.asarray(bm.grouped_planar_bitmatrix(
            np.ascontiguousarray(mat).tobytes(), M, K, g))
        label = f"xla-g{g}"
        assert check(
            functools.partial(bm.gf_matmul_xla_grouped, Bg, group=g),
            label)
        results[label] = measure(
            lambda d, i, Bg=Bg, g=g: bm.gf_matmul_xla_grouped(
                Bg, d, group=g), label)
        for tn in (2048, 8192):
            label = f"pallas-g{g}-t{tn}"
            try:
                assert check(
                    functools.partial(bm.gf_matmul_pallas_grouped, Bgp,
                                      group=g, tile_n=tn), label)
                results[label] = measure(
                    lambda d, i, Bgp=Bgp, g=g, tn=tn:
                    bm.gf_matmul_pallas_grouped(Bgp, d, group=g,
                                                tile_n=tn), label)
            except Exception as ex:
                print(f"{label}: failed: {type(ex).__name__}: "
                      f"{str(ex)[:120]}")

    # the public auto-selecting entry (what the plugin runs)
    try:
        assert check(lambda d: bm.gf_matmul_pallas(mat, d), "pallas-auto")
        results["pallas-auto"] = measure(
            lambda d, i: bm.gf_matmul_pallas(mat, d), "pallas-auto")
    except Exception as ex:
        print(f"pallas-auto failed: {ex}")

    best = max(results, key=results.get)
    print(f"\nbest: {best} at {results[best]:.1f} GB/s "
          f"({results[best] / results['xla']:.2f}x over xla baseline)")


if __name__ == "__main__":
    main()
