"""Compile rehearsals: every kernel on the main path, compiled for a TPU v5e.

The TPU compiler is installed with jax and compiles for a chip that is
described, not attached, so these tests catch what interpret mode
cannot — block shapes off the (8, 128) tiling, relayouts Mosaic does not
lower, VMEM overruns — at the shapes ``chip_smoke.py`` runs.  Nothing
executes: a compile that passes is not a chip run.

The topology is described inside a module fixture (never at import), so
only the pytest worker that runs this file loads the TPU library; the
tests skip where no v5e topology can be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:   # no libtpu, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def test_described_chip_has_peak_terms(topo):
    from repro.launch import mesh
    assert mesh.chip_peaks(topo.devices[0])["hbm_bw"] == 819e9


@pytest.mark.parametrize("h,w,transform", [(512, 512, "exact"),
                                           (512, 512, "cordic"),
                                           (512, 480, "exact")])
def test_fused_codec_compiles(shape, h, w, transform):
    from repro.kernels import common
    from repro.kernels.fused_codec import kernel
    th, tw = common.tile_shape(h, w, 256)
    hlo = _compile(
        lambda x, q: kernel.fused_codec_pallas(
            x, q, tile_h=th, tile_w=tw, transform=transform,
            interpret=False),
        shape((h, w), jnp.float32), shape((th, tw), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("inverse", [False, True])
def test_dct8x8_compiles(shape, inverse):
    from repro.kernels.dct8x8 import kernel
    hlo = _compile(
        lambda x: kernel.dct8x8_pallas(x, tile_h=256, tile_w=256,
                                       inverse=inverse, interpret=False),
        shape((512, 512), jnp.float32))
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("n_blocks", ["1024", "MAX_DEVICE_BLOCKS"])
def test_symbolize_compiles(shape, n_blocks):
    from repro.kernels.symbolize import kernel, ops
    n = ops.MAX_DEVICE_BLOCKS if n_blocks == "MAX_DEVICE_BLOCKS" else 1024
    hlo = _compile(
        lambda d, a, r: kernel.symbolize_pallas(
            d, a, r, tile_blocks=ops.TILE_BLOCKS, interpret=False),
        shape((n, 1)), shape((n, 63)), shape((1,)))
    assert "tpu_custom_call" in hlo


def test_symbolize_two_classes_compiles(shape):
    # a colour stream: per-block table class, two histogram rows
    from repro.kernels.symbolize import kernel, ops
    n = ops.MAX_DEVICE_BLOCKS
    hlo = _compile(
        lambda d, a, r, c: kernel.symbolize_pallas(
            d, a, r, c, tile_blocks=ops.TILE_BLOCKS, n_classes=2,
            interpret=False),
        shape((n, 1)), shape((n, 63)), shape((1,)), shape((n, 1)))
    assert "tpu_custom_call" in hlo


def test_pack_bits_compiles(shape):
    from repro.kernels.pack_bits import kernel, ops
    tile_bits = ops.TILE_BITS
    m = ops.field_blocks(ops.MAX_DEVICE_FIELDS, tile_bits)
    n_tiles = ops.MAX_DEVICE_FIELDS * 16 // tile_bits
    hlo = _compile(
        lambda f, b: kernel.pack_bits_pallas(
            f, b, tile_bits=tile_bits, window=ops.WINDOW, interpret=False),
        shape((3, m)), shape((n_tiles,)))
    assert "tpu_custom_call" in hlo


def test_unpack_bits_compiles(shape):
    from repro.kernels.unpack_bits import kernel, ops
    n = ops._pow2(ops.MAX_DEVICE_BITS + 1 + kernel.MAX_ADV)
    hlo = _compile(
        lambda p, w: kernel.unit_words_pallas(p, w, interpret=False),
        shape((kernel.N_PARAMS,)), shape((n // kernel.LANES, kernel.LANES)))
    assert "tpu_custom_call" in hlo
    words = shape((n // kernel.LANES, kernel.LANES))
    hlo = _compile(
        lambda d, a: kernel.stage_tiles(d, a), words, words)
    # the chain walk reads its words at static shifts: no gather
    assert " gather(" not in hlo


@pytest.mark.parametrize("n_blocks,classes", [
    ("MAX_DEVICE_BLOCKS", (0,)),
    (9216, (0, 0, 0, 0, 1, 1)),         # a Kodak photo: 4:2:0, two classes
])
def test_unpack_resolve_compiles(shape, n_blocks, classes):
    # the engine's whole-stream decode at the payload guard: unit words,
    # the chain walk, values, and the scalar resolver over SMEM windows
    from repro.kernels.unpack_bits import kernel, ops
    if n_blocks == "MAX_DEVICE_BLOCKS":
        n_blocks = ops.MAX_DEVICE_BLOCKS
    n = ops._pow2(ops.MAX_DEVICE_BITS + 1 + kernel.MAX_ADV)
    n_cls = max(classes) + 1
    hlo = _compile(
        lambda p, w: kernel.unit_words_resolve(
            p, w, block_rows=-(-n_blocks // kernel.GROUP), classes=classes,
            interpret=False),
        shape((1 + n_cls * kernel.N_PARAMS,)),
        shape((n // kernel.LANES, kernel.LANES)))
    assert hlo.count("tpu_custom_call") >= 2
    assert " gather(" not in hlo


def test_engine_compress_compiles(shape):
    from repro.core import cordic
    from repro.serve import codec_engine
    _compile(
        lambda x: codec_engine._compress_sharded(
            x, "exact", 50, cordic.PAPER_CONFIG, 1),
        shape((8, 512, 512), jnp.uint8))


@pytest.mark.parametrize("h,w", [(512, 768), (768, 512)])
def test_engine_colour_programs_compile(shape, h, w):
    # the Kodak sizes: 8 images per padded batch, as the colour cell runs
    from repro.core import cordic
    from repro.serve import codec_engine
    _compile(
        lambda x: codec_engine._compress_sharded_colour(
            x, "exact", 75, cordic.PAPER_CONFIG, 1),
        shape((8, h, w, 3), jnp.uint8))
    _compile(
        lambda z: codec_engine._decompress_sharded_colour(
            z, "exact", 75, cordic.PAPER_CONFIG, 1),
        shape((8, h // 16, w // 16, 6, 64)))
