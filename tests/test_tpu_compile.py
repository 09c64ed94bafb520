"""The EC kernels of the main path compile for a described v5e chip at
the widths chip_smoke.py and the benchmark serve (k=8, m=4: 256
stripes x 128 KiB chunks, and a degraded 4 MiB read's 128 stripes x
4 KiB; the lrc and clay pools' shapes), and where the persistent
compile cache lives.

Compiling needs no chip, only the TPU compiler; nothing runs, so this
says nothing of results or times.  The topology is described inside a
fixture, never at import: one process at a time may load libtpu."""
import os

import jax
import jax.numpy as jnp
import pytest

from ceph_tpu.common import compile_cache
from ceph_tpu.ec.kernels import bitmatmul as bm

K, M = 8, 4
STRIPES, CHUNK = 256, 128 * 1024


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


@pytest.mark.parametrize("rows,stripes,chunk,group,tile", [
    pytest.param(M, STRIPES, CHUNK, 4, 8192, id="4-8192"),
    pytest.param(M, STRIPES, CHUNK, 1, 2048, id="1-2048"),
    # one lost data chunk of ec-k8m4.read-degraded-4m: the (1 x 8)
    # decode matrix over a 4 MiB object's 128 stripes
    pytest.param(1, 128, 4096, 4, 2048, id="decode-1x8"),
])
def test_encode_kernel_compiles(one_chip, rows, stripes, chunk, group,
                                tile):
    bgp = shape(one_chip, (8 * rows * group, 8 * K * group), jnp.int8)
    data = shape(one_chip, (stripes, K, chunk), jnp.uint8)
    compiled = bm.gf_matmul_pallas_grouped.lower(
        bgp, data, group=group, tile_n=tile).compile()
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


@pytest.mark.parametrize("r,k,group", [(4, 4, 4), (1, 3, 4), (2, 4, 4),
                                       (4, 4, 1), (1, 3, 1)])
def test_lrc_kernels_compile(one_chip, r, k, group):
    """The lrc k=4 m=2 l=3 pool's batched shapes at its 4 KiB chunks:
    the 4x4 encode, the 1x3 local decode and a 2x4 global decode, over
    256 stripes (a 4 MiB object) and over one."""
    bgp = shape(one_chip, (8 * r * group, 8 * k * group), jnp.int8)
    data = shape(one_chip, (256 if group == 4 else 1, k, 4096), jnp.uint8)
    compiled = bm.gf_matmul_pallas_grouped.lower(
        bgp, data, group=group, tile_n=2048).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("r,k,stripes", [(16, 32, 256), (8, 20, 256),
                                          (16, 32, 1)])
def test_clay_kernels_compile(one_chip, r, k, stripes):
    """The clay k=4 m=2 d=5 pool's batched shapes at its 4 KiB chunks,
    8 planes of 512 B each: the 16x32 plane encode and the 8x20 repair
    of one lost chunk from 5 helpers' 4 repair planes, over 256 stripes
    (a 4 MiB object, 16 stripes to a grid cell) and over one."""
    group, tile, block = bm.pallas_layout(stripes, k, 512)
    bgp = shape(one_chip, (8 * r * group, 8 * k * group), jnp.int8)
    data = shape(one_chip, (stripes, k, 512), jnp.uint8)
    compiled = bm.gf_matmul_pallas_grouped.lower(
        bgp, data, group=group, tile_n=tile, block=block).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_crush_narrow_draws_compile_to_row_gathers(one_chip):
    """A host draw (20 OSDs) under a 50-host root reads its 20-lane rows
    as native gathers.  A partial-row read there compiles into a loop of
    per-PG dynamic slices on the TPU, though the CPU runs it fine."""
    from ceph_tpu.crush import batch
    from ceph_tpu.osd.osdmap import OSDMap
    om = OSDMap()
    om.build_simple(1000, osds_per_host=20)
    cc = batch.compile_map(om.crush)
    static = cc.static_cfg(0, 3)
    assert static.draw_widths == ((50, 20),)
    with jax.enable_x64(True):
        tables = tuple(shape(one_chip, a.shape, a.dtype)
                       for a in cc.arrays())
        hlo = batch._rule_fn(static).lower(
            tables, shape(one_chip, (4096,), jnp.int64),
            shape(one_chip, (om.max_osd,), jnp.int64)).compile().as_text()
    assert "slice_sizes={1,20}" in hlo
    assert "dynamic-slice" not in hlo
