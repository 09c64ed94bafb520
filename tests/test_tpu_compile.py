"""The EC kernels of the main path compile for a described v5e chip at
bench widths (k=8, m=4, 256 stripes x 128 KiB chunks), and where the
persistent compile cache lives.

Compiling needs no chip, only the TPU compiler; nothing runs, so this
says nothing of results or times.  The topology is described inside a
fixture, never at import: one process at a time may load libtpu."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ceph_tpu.common import compile_cache
from ceph_tpu.ec import gf
from ceph_tpu.ec.kernels import bitmatmul as bm
from ceph_tpu.ec.matrix_code import make_decode_matrix_full

K, M = 8, 4
STRIPES, CHUNK = 256, 128 * 1024
ENC = gf.isa_rs_matrix(K, M)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def shape(one_chip, shp, dtype):
    return jax.ShapeDtypeStruct(shp, dtype, sharding=one_chip)


@pytest.mark.parametrize("group,tile", [(4, 8192), (1, 2048)])
def test_encode_kernel_compiles(one_chip, group, tile):
    bgp = shape(one_chip, (8 * M * group, 8 * K * group), jnp.int8)
    data = shape(one_chip, (STRIPES, K, CHUNK), jnp.uint8)
    compiled = bm.gf_matmul_pallas_grouped.lower(
        bgp, data, group=group, tile_n=tile).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_decode_kernel_compiles(one_chip):
    erasures = [1, 9]
    valid = np.ones(K + M, dtype=bool)
    valid[erasures] = False
    survivors = [i for i in range(K + M) if valid[i]][:K]
    full = make_decode_matrix_full(ENC, K, K + M, survivors, erasures)
    sel = tuple(bm.selection_from_matrix(full, valid))
    group = 4
    bgp = shape(one_chip, (8 * len(erasures) * group, 8 * K * group),
                jnp.int8)
    data = shape(one_chip, (STRIPES, K + M, CHUNK), jnp.uint8)
    compiled = bm.gf_decode_pallas_grouped_full.lower(
        bgp, data, sel=sel, n=K + M, group=group,
        tile_n=8192).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cache_dir_from_env_is_left_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_unset_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        got = compile_cache.use_compile_cache()
        assert got == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
