#!/usr/bin/env python
"""Driver benchmark: north-star metric, one JSON line on stdout.

Metric (BASELINE.md): `ceph_erasure_code_benchmark` semantics at k=8, m=4,
1 MiB objects — encode + decode (2 erasures) MB/s on the `tpu` erasure-code
plugin, chunks byte-identical to the CPU reference plugins
(ref: src/test/erasure-code/ceph_erasure_code_benchmark.cc:151-181,246-312).

The measurement drives the PUBLIC plugin API — `encode_batch` /
`decode_batch` on the registry-created plugin (including the survivor
gather on the decode side) — not a raw kernel.

vs_baseline divides by a MEASURED single-core CPU floor: an AVX2
split-nibble PSHUFB encode (native/gf_avx2.c — the scheme ISA-L's
ec_encode_data assembly uses) compiled and timed at bench time, with the
repo's numpy `isa` plugin timed alongside.  A failed compile is an error.

Runs only on a TPU: with any other JAX platform it exits non-zero before
measuring anything.  Each measurement chains R unique encodes (input
xor'd with the step index) inside one jitted lax.scan and reads back a
single scalar, so one host sync covers R device dispatches.
"""
import ctypes
import json
import os
import subprocess
import sys
import time

import numpy as np

K, M = 8, 4
OBJECT_SIZE = 1 << 20            # 1 MiB
CHUNK = OBJECT_SIZE // K         # 131072
STRIPES = 256                    # stripes per dispatch (256 MiB in HBM)
REPS = 100                       # scan-chained unique reps per timing
REPEATS = 3                      # timed runs per kernel: median + spread


def measure_cpu_avx2(mat: np.ndarray, data_rows: list) -> float:
    """Compile native/gf_avx2.c and time it; MB/s data-in."""
    root = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(root, "native", "gf_avx2.c")
    lib_dir = os.path.join(root, "ceph_tpu", "_native")   # .gitignore'd
    lib_path = os.path.join(lib_dir, "libgfavx2_bench.so")
    os.makedirs(lib_dir, exist_ok=True)
    subprocess.run(["cc", "-O3", "-mavx2", "-shared", "-fPIC",
                    "-o", lib_path, src], check=True,
                   capture_output=True, timeout=60)
    lib = ctypes.CDLL(lib_path)
    out_rows = [np.zeros(CHUNK, dtype=np.uint8) for _ in range(M)]
    pp = ctypes.POINTER(ctypes.c_uint8)
    darr = (pp * K)(*[d.ctypes.data_as(pp) for d in data_rows])
    oarr = (pp * M)(*[o.ctypes.data_as(pp) for o in out_rows])
    cmat = np.ascontiguousarray(mat)

    def run():
        lib.gf_encode_avx2(K, M, ctypes.c_long(CHUNK),
                           cmat.ctypes.data_as(pp), darr, oarr)

    run()
    # the baseline denominator must itself be correct
    from ceph_tpu.ec import gf
    want = gf.gf_matmul_bytes(cmat, np.stack(data_rows))
    if not all(np.array_equal(out_rows[i], want[i]) for i in range(M)):
        raise AssertionError("AVX2 baseline encode differs from gf oracle")
    reps = 30
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    dt = (time.perf_counter() - t0) / reps
    return K * CHUNK / dt / 1e6


def measure_cpu_numpy_isa(obj: bytes) -> float:
    """Time the repo's numpy `isa` plugin encode (MB/s data-in)."""
    from ceph_tpu.ec import registry
    isa = registry.factory("isa", {"k": str(K), "m": str(M),
                                   "technique": "reed_sol_van"})
    want = set(range(K + M))
    isa.encode(want, obj)  # warm
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        isa.encode(want, obj)
    dt = (time.perf_counter() - t0) / reps
    return OBJECT_SIZE / dt / 1e6


def repair_read_ratio() -> float:
    """Simulated single-shard rebuild on a clay (regenerating) pool:
    bytes actually shipped by the sub-chunk repair path vs the k
    whole chunks a full-chunk rebuild reads.  Runs a REAL (tiny)
    repair through ecutil.repair_shard_stream and asserts the rebuilt
    shard is byte-identical before reporting the ratio (the cluster
    counterpart is the recovery_bytes_read perf counter asserted by
    scripts/recovery_smoke.py)."""
    from ceph_tpu.ec import registry
    from ceph_tpu.osd import ecutil as osd_ecutil
    clay = registry.factory("clay", {"k": str(K), "m": str(M)})
    cs = clay.get_chunk_size(K * 4096)
    sinfo = osd_ecutil.StripeInfo(K, K * cs)
    rng = np.random.default_rng(3)
    logical = rng.integers(0, 256, 2 * sinfo.stripe_width,
                           dtype=np.uint8).tobytes()
    shards = osd_ecutil.encode(sinfo, clay, logical)
    lost = 1
    helpers = clay.minimum_to_repair(
        {lost}, set(range(K + M)) - {lost})
    extents = osd_ecutil.repair_chunk_extents(clay, lost, cs)
    helper_bufs = {}
    for s in helpers:
        stream = shards[s]
        helper_bufs[s] = b"".join(
            stream[off:off + ln] for off, ln in
            osd_ecutil.expand_stream_extents(extents, cs, len(stream)))
    rebuilt = osd_ecutil.repair_shard_stream(clay, cs, lost,
                                             helper_bufs)
    assert rebuilt == shards[lost], "sub-chunk repair parity"
    sub_bytes = sum(len(v) for v in helper_bufs.values())
    full_bytes = K * len(shards[lost])
    return round(sub_bytes / full_bytes, 4)


def main() -> None:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from ceph_tpu.common.compile_cache import use_compile_cache
    from ceph_tpu.ec import registry

    use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py: JAX found no TPU (platform "
                         f"{dev.platform!r}); there is no CPU fallback")

    # --- correctness gate: chunks byte-identical to the CPU oracle ----
    tpu = registry.factory("tpu", {"k": str(K), "m": str(M)})
    rng = np.random.default_rng(0)
    obj = rng.integers(0, 256, OBJECT_SIZE, dtype=np.uint8).tobytes()
    encoded = tpu.encode(set(range(K + M)), obj)
    cpu = registry.factory("isa", {"k": str(K), "m": str(M),
                                   "technique": "reed_sol_van"})
    encoded_cpu = cpu.encode(set(range(K + M)), obj)
    for i in range(K + M):
        if not np.array_equal(encoded[i], encoded_cpu[i]):
            print(json.dumps({"metric": "ec_encode_decode_MBps_k8m4_1MiB",
                              "value": 0.0, "unit": "MB/s",
                              "vs_baseline": 0.0,
                              "error": f"chunk {i} parity mismatch"}))
            sys.exit(1)
    avail = {i: encoded[i] for i in range(K + M) if i not in (1, 9)}
    decoded = tpu.decode(set(range(K + M)), avail)
    assert all(np.array_equal(decoded[i], encoded[i]) for i in range(K + M))

    # --- device-side throughput through the plugin API ----------------
    data = jnp.asarray(
        rng.integers(0, 256, (STRIPES, K, CHUNK), dtype=np.uint8))

    # encode: the public batched API (one dispatch per batch)
    @jax.jit
    def chained_encode(d):
        def body(c, i):
            parity = tpu.encode_batch(d ^ i)
            return c + jnp.sum(parity, dtype=jnp.int32), None
        acc, _ = lax.scan(body, jnp.int32(0),
                          jnp.arange(REPS, dtype=jnp.uint8))
        return acc

    # decode: erase data chunk 1 + parity chunk 9.  TWO decode legs:
    # * staged (`decode_MBps`): the dense (S, k, N) survivor layout as
    #   reply assembly produces it, matmul against the cached
    #   per-signature decode matrix (ISA-L table-cache analogue,
    #   ref: ErasureCodeIsa.cc:252-306);
    # * staging-free (`decode_incl_stage_MBps`): decode_batch_full on
    #   the (S, k+m, N) chunk array in ARRIVAL layout — the zero-column
    #   full matrix + in-kernel survivor selection
    #   (bitmatmul.GFDecodeFull), so the survivor gather does not
    #   exist on host OR device.  This leg IS what a degraded read
    #   pays end to end, hence it feeds the headline combined metric
    #   (the r05 headline averaged the staged-out decode, overstating
    #   the system number: decode 76.7 vs decode_incl_stage 35.4 GB/s).
    erasures = [1, 9]
    decode_index = [0, 2, 3, 4, 5, 6, 7, 8]
    sel = jnp.asarray(decode_index, dtype=jnp.int32)
    parity0 = tpu.encode_batch(data)
    all_chunks = jnp.concatenate([data, parity0], axis=1)  # (S, k+m, N)
    survivors0 = jnp.asarray(all_chunks[:, sel, :])        # staged once
    # correctness: both decode paths rebuild the erased chunks exactly
    rec0 = np.asarray(tpu.decode_batch(decode_index, erasures,
                                       survivors0))
    assert np.array_equal(rec0[:, 0], np.asarray(data[:, 1]))
    assert np.array_equal(rec0[:, 1], np.asarray(parity0[:, 1]))
    recf = np.asarray(tpu.decode_batch_full(erasures, all_chunks))
    assert np.array_equal(recf, rec0)

    @jax.jit
    def chained_decode(survivors):
        def body(c, i):
            rec = tpu.decode_batch(decode_index, erasures,
                                   survivors ^ i)
            return c + jnp.sum(rec, dtype=jnp.int32), None
        acc, _ = lax.scan(body, jnp.int32(0),
                          jnp.arange(REPS, dtype=jnp.uint8))
        return acc

    @jax.jit
    def chained_decode_full(chunks):
        def body(c, i):
            # the xor perturbs ALL slots including the erased ones:
            # the zero columns must ignore arbitrary garbage
            rec = tpu.decode_batch_full(erasures, chunks ^ i)
            return c + jnp.sum(rec, dtype=jnp.int32), None
        acc, _ = lax.scan(body, jnp.int32(0),
                          jnp.arange(REPS, dtype=jnp.uint8))
        return acc

    def measure(fn, arg):
        """>= REPEATS timed runs (after compile+warm); returns the
        per-dispatch seconds of every repeat.  The clock stops only
        after jax.block_until_ready — float() also forces the scalar,
        but block_until_ready is the EXPLICIT device sync (cephck
        jax-timing), so the timed region can never silently become
        dispatch-only if the reduction is refactored away."""
        jax.block_until_ready(fn(arg))  # compile + warm
        out = []
        for _ in range(REPEATS):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(arg))
            out.append((time.perf_counter() - t0) / REPS)
        return out

    import statistics

    enc_times = measure(chained_encode, data)
    dec_times = measure(chained_decode, survivors0)
    dec_full_times = measure(chained_decode_full, all_chunks)
    t_enc = statistics.median(enc_times)
    t_dec = statistics.median(dec_times)
    t_dec_full = statistics.median(dec_full_times)

    # honest staging cost (VERDICT r4 weak #7): the survivor gather
    # from the full chunk array into the dense (S, k, N) layout —
    # outside the timed decode loop because the real read path pays
    # it once at reply assembly, but reported alongside so the decode
    # number can't read as staging-free
    @jax.jit
    def chained_stage(chunks):
        def body(c, i):
            # optimization_barrier forces the dense survivor layout to
            # MATERIALIZE: without it XLA fuses the static gather into
            # the reduce and the "stage" never writes HBM, reporting a
            # copy rate ~2x what reply assembly actually sustains
            sv = lax.optimization_barrier((chunks ^ i)[:, sel, :])
            return c + jnp.sum(sv, dtype=jnp.int32), None
        acc, _ = lax.scan(body, jnp.int32(0),
                          jnp.arange(REPS, dtype=jnp.uint8))
        return acc

    stage_times = measure(chained_stage, all_chunks)
    t_stage = statistics.median(stage_times)

    # --- measured CPU floor -------------------------------------------
    mat = tpu.encode_matrix[K:]
    data_rows = [np.ascontiguousarray(np.asarray(data[0, j]))
                 for j in range(K)]
    baseline = measure_cpu_avx2(mat, data_rows)
    numpy_mbps = measure_cpu_numpy_isa(obj)

    total_mb = STRIPES * OBJECT_SIZE / 1e6
    # per-repeat combined metric (encode pass + the STAGING-FREE
    # decode pass), so the spread of the HEADLINE number is what gets
    # reported — decode_incl_stage is the system number a degraded
    # read pays, not the staged-out kernel time
    values = [2 * total_mb / (te + td)
              for te, td in zip(enc_times, dec_full_times)]
    value = statistics.median(values)
    stddev = statistics.pstdev(values)
    print(json.dumps({
        "metric": "ec_encode_decode_MBps_k8m4_1MiB",
        "value": round(value, 1),
        "unit": "MB/s",
        "repeats": REPEATS,
        "median": round(value, 1),
        "stddev": round(stddev, 2),
        "vs_baseline": round(value / baseline, 2),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "detail": {
            "encode_MBps": round(total_mb / t_enc, 1),
            "decode_MBps": round(total_mb / t_dec, 1),
            "stage_MBps": round(total_mb / t_stage, 1),
            # staging-free full-width decode: survivor selection baked
            # into the zero-column decode matrix, gather in-kernel —
            # there is no stage, so incl-stage IS the kernel time
            "decode_incl_stage_MBps": round(total_mb / t_dec_full, 1),
            "decode_staged_incl_stage_MBps": round(
                total_mb / (t_dec + t_stage), 1),
            "repair_read_ratio": repair_read_ratio(),
            # per-kernel medians + spread across REPEATS timed runs
            "encode_MBps_stddev": round(
                statistics.pstdev([total_mb / t for t in enc_times]),
                2),
            "decode_MBps_stddev": round(
                statistics.pstdev([total_mb / t for t in dec_times]),
                2),
            "decode_incl_stage_MBps_stddev": round(
                statistics.pstdev(
                    [total_mb / t for t in dec_full_times]), 2),
            "stage_MBps_stddev": round(
                statistics.pstdev([total_mb / t for t in stage_times]),
                2),
            "stripes_per_dispatch": STRIPES,
            "api": "plugin encode_batch/decode_batch_full (arrival-"
                   "layout chunk array, device-resident survivor "
                   "selection; staged decode_batch reported alongside; "
                   "cached per-signature decode matrices in HBM)",
            "chunk_parity_with_cpu_reference": True,
            "baseline_MBps": round(baseline, 1),
            "baseline": "measured AVX2 pshufb encode (native/gf_avx2.c)",
            "cpu_numpy_isa_MBps": round(numpy_mbps, 1),
        },
    }))


if __name__ == "__main__":
    main()
