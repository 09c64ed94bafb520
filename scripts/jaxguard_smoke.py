#!/usr/bin/env python
"""jaxguard smoke — the device-contract half of the ship gate.

One batched EC encode/decode pair through osd/ecutil (the served
path: encode_batch and decode_batch inside the transfer guard) run
TWICE with identical shapes, asserting:

* **exactly-once compilation per signature**: every jit callsite's
  compile count equals its distinct-signature count after round 1,
  and round 2 adds ZERO compiles (pure cache hits) — the
  jit-retrace-churn class cannot ship through this gate;
* **zero unintended transfers**: the dispatches run inside
  jax.transfer_guard('disallow') (armed because CEPH_TPU_JAXGUARD=1),
  so any implicit host<->device copy would have raised;
* **zero recompiles** anywhere (the RecompileError bound of 0 held).

Exit 0 = green.  Wired into scripts/check_green.sh before the suite.
"""
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["CEPH_TPU_JAXGUARD"] = "1"

from ceph_tpu.common import jaxguard  # noqa: E402

jaxguard.enable_if_configured()

import numpy as np  # noqa: E402

from ceph_tpu.ec.registry import ErasureCodePluginRegistry  # noqa: E402
from ceph_tpu.osd import ecutil  # noqa: E402

K, M = 4, 2
STRIPES = 8


def total_compiles(st):
    return sum(v["compiles"] for v in st.values())


def one_pair(ec, sinfo, data):
    """One batched encode, then a decode of one data and one parity
    shard from the survivors, both through ecutil."""
    shards = ecutil.encode(sinfo, ec, data)
    have = {i: shards[i] for i in range(K + M) if i not in (1, K)}
    got = ecutil.decode(sinfo, ec, have, want=[1, K])
    assert got[1] == shards[1] and got[K] == shards[K], \
        "decode mismatch"


def main() -> int:
    if not jaxguard.enabled():
        print("jaxguard smoke: FAIL (sanitizer did not arm)")
        return 1
    ec = ErasureCodePluginRegistry.instance().factory(
        "tpu", {"k": str(K), "m": str(M)})
    cs = ec.get_chunk_size(K * 4096)
    sinfo = ecutil.StripeInfo(K, K * cs)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, STRIPES * K * cs,
                        dtype=np.uint8).tobytes()

    one_pair(ec, sinfo, data)           # round 1: compiles
    st1 = jaxguard.stats()
    for key, s in st1.items():
        if s["recompiles"]:
            print(f"jaxguard smoke: FAIL recompiles at {key}: {s}")
            return 1
        if s["compiles"] != s["signatures"]:
            print(f"jaxguard smoke: FAIL compiles != signatures "
                  f"at {key}: {s}")
            return 1

    one_pair(ec, sinfo, data)           # round 2: pure cache hits
    st2 = jaxguard.stats()
    if total_compiles(st2) != total_compiles(st1):
        grew = {k: (st1.get(k, {}).get("compiles", 0), v["compiles"])
                for k, v in st2.items()
                if v["compiles"] != st1.get(k, {}).get("compiles", 0)}
        print(f"jaxguard smoke: FAIL round 2 recompiled: {grew}")
        return 1
    for key, s in st2.items():
        if s["recompiles"]:
            print(f"jaxguard smoke: FAIL recompiles at {key}: {s}")
            return 1

    sites = sum(1 for v in st2.values() if v["calls"])
    print(f"jaxguard smoke: OK ({sites} jit callsites, "
          f"{total_compiles(st2)} compiles, all exactly-once per "
          f"signature; transfer guard clean on encode/decode)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
