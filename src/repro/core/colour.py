"""Colour stage: baseline YCbCr 4:2:0 around the blockwise codec.

A colour image is coded as three component planes, the way ITU-T T.81
baseline JPEG codes a JFIF file:

* **conversion** — JFIF 1.02's full-range BT.601 RGB -> YCbCr, each
  component level-shifted by -128 for the DCT;
* **sampling** — 4:2:0, i.e. H = V = 2 for Y and 1 for Cb and Cr
  (T.81 A.1.1): each chroma sample is the mean of a 2x2 pixel square;
* **transform and quantisation** — the codec's blockwise DCT
  (:func:`repro.core.codec._forward`, any transform) per plane, Y with
  the Annex K luminance table and Cb, Cr with the chrominance table;
* **MCU interleave** — the image is padded by edge replication to
  16x16 minimum coded units; each MCU contributes six blocks, in the
  order ``Y00 Y01 Y10 Y11 Cb Cr`` (:data:`COMPONENT_OF_BLOCK`), each in
  zig-zag order, MCUs in raster order.

Decode inverts each step: dequantise, inverse transform per plane,
upsample chroma with IJG's default triangle ("fancy") h2v2 filter —
each output sample weighs its nearest chroma sample 3/4 and the next
one 1/4 along each axis, edges replicated at the plane's border — then
YCbCr -> RGB, round and clip.

Departures from libjpeg, which rounds component samples to 8 bits
between stages: samples stay float32 from conversion to quantisation
and from the inverse transform to the final RGB round and clip, the
2x2 mean and the upsampling filter carry no integer rounding bias, and
the upsampler's border is the MCU-padded plane's, not the image's.

Everything here is batch-first ``jnp`` on fixed shapes, so the engine
traces it inside its sharded programs
(:mod:`repro.serve.codec_engine`); :func:`encode_image` /
:func:`repro.core.entropy.decode_image` are the single-image forms.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec, cordic, quant
from repro.core.entropy import container, scan

MCU = 16                               # pixels per MCU side at 4:2:0
#: Component of each block of an MCU (Y00 Y01 Y10 Y11 Cb Cr): 0 Y, 1 Cb,
#: 2 Cr.
COMPONENT_OF_BLOCK = container.COLOUR_BLOCK_COMPONENTS
#: Huffman table class of each block of an MCU: 0 luma, 1 chroma.
TABLE_CLASSES = container.COLOUR_BLOCK_CLASSES
BLOCKS_PER_MCU = len(COMPONENT_OF_BLOCK)

# JFIF 1.02: Y, Cb - 128 and Cr - 128 from R, G, B (rows), and back
_TO_YCBCR = ((0.299, 0.587, 0.114),
             (-0.1687, -0.3313, 0.5),
             (0.5, -0.4187, -0.0813))
_CR_TO_R, _CB_TO_G, _CR_TO_G, _CB_TO_B = 1.402, 0.34414, 0.71414, 1.772


def mcu_grid(height: int, width: int) -> tuple:
    """(MCU rows, MCU columns) of an image, padded to 16x16 MCUs."""
    return -(-height // MCU), -(-width // MCU)


def is_colour(img) -> bool:
    """True for an (..., H, W, 3) image array (one or a batch)."""
    shape = getattr(img, "shape", ())
    return len(shape) >= 3 and shape[-1] == 3


def pad_to_mcu(rgb: jnp.ndarray) -> jnp.ndarray:
    """Edge-replicate the (H, W) axes of (..., H, W, 3) to multiples of 16."""
    h, w = rgb.shape[-3:-1]
    ph, pw = (-h) % MCU, (-w) % MCU
    if ph == 0 and pw == 0:
        return rgb
    pad = [(0, 0)] * (rgb.ndim - 3) + [(0, ph), (0, pw), (0, 0)]
    return jnp.pad(rgb, pad, mode="edge")


def _downsample(plane: jnp.ndarray) -> jnp.ndarray:
    """(B, H, W) -> (B, H/2, W/2): mean of each 2x2 square."""
    b, h, w = plane.shape
    return plane.reshape(b, h // 2, 2, w // 2, 2).mean(axis=(2, 4))


def _upsample_axis(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Triangle-filter doubling along ``axis``: output 2i weighs sample i
    3/4 and i-1 1/4, output 2i+1 weighs i 3/4 and i+1 1/4; the border
    sample stands in for its missing neighbour."""
    n = x.shape[axis]
    cut = functools.partial(jax.lax.slice_in_dim, x, axis=axis)
    prev = jnp.concatenate([cut(0, 1), cut(0, n - 1)], axis=axis)
    nxt = jnp.concatenate([cut(1, n), cut(n - 1, n)], axis=axis)
    lo = 0.75 * x + 0.25 * prev
    hi = 0.75 * x + 0.25 * nxt
    out = jnp.stack([lo, hi], axis=axis + 1)
    shape = list(x.shape)
    shape[axis] = 2 * n
    return out.reshape(shape)


def _upsample(plane: jnp.ndarray) -> jnp.ndarray:
    """(B, h, w) -> (B, 2h, 2w): IJG's h2v2 "fancy" upsampling."""
    return _upsample_axis(_upsample_axis(plane, 1), 2)


def _plane_forward(plane, transform, cordic_config):
    """(B, H, W) level-shifted samples -> (B, H/8, W/8, 8, 8) coefficients."""
    return jax.vmap(lambda x: codec._forward(x, transform, cordic_config))(
        plane)


def _plane_inverse(coeffs, transform, cordic_config):
    """(B, gh, gw, 8, 8) coefficients -> (B, 8gh, 8gw) samples."""
    return jax.vmap(lambda c: codec._inverse(c, transform, cordic_config))(
        coeffs)


def compress_batch_mcus(imgs: jnp.ndarray, transform: codec.Transform,
                        quality: int,
                        cordic_config: cordic.CordicConfig) -> jnp.ndarray:
    """Batch-first body: (B, H, W, 3) RGB -> interleaved zig-zag levels.

    Plain (unjitted) so the engine traces it inside shard_map.

    Args:
        imgs: (B, H, W, 3) uint8/float RGB batch, H and W multiples of 16
            (see :func:`pad_to_mcu`).
        transform: forward transform ("exact", "cordic", "loeffler").
        quality: JPEG quality factor in [1, 100].
        cordic_config: CORDIC config (``transform == "cordic"`` only).

    Returns:
        (B, H/16, W/16, 6, 64) int32: per MCU its blocks ``Y00 Y01 Y10
        Y11 Cb Cr``, each in zig-zag order.
    """
    x = imgs.astype(jnp.float32)
    r, g, b_ = x[..., 0], x[..., 1], x[..., 2]
    y, cb, cr = (k[0] * r + k[1] * g + k[2] * b_ for k in _TO_YCBCR)
    y = y - 128.0
    cb, cr = _downsample(cb), _downsample(cr)
    yq = quant.quantize(_plane_forward(y, transform, cordic_config),
                        quant.qtable(quality))
    qc = quant.qtable(quality, chroma=True)
    cbq = quant.quantize(_plane_forward(cb, transform, cordic_config), qc)
    crq = quant.quantize(_plane_forward(cr, transform, cordic_config), qc)
    b, mh, mw = cbq.shape[:3]
    # (B, 2mh, 2mw, 8, 8) -> (B, mh, mw, 4, 64): Y00 Y01 Y10 Y11 per MCU
    yb = yq.reshape(b, mh, 2, mw, 2, 64).transpose(0, 1, 3, 2, 4, 5)
    blocks = jnp.concatenate([yb.reshape(b, mh, mw, 4, 64),
                              cbq.reshape(b, mh, mw, 1, 64),
                              crq.reshape(b, mh, mw, 1, 64)], axis=3)
    return blocks[..., jnp.asarray(scan.zigzag_perm())]


def decompress_batch_mcus(z: jnp.ndarray, transform: codec.Transform,
                          quality: int,
                          cordic_config: cordic.CordicConfig) -> jnp.ndarray:
    """Batch-first body: interleaved zig-zag levels -> (B, H, W, 3) uint8.

    Args:
        z: (B, mh, mw, 6, 64) int32 levels as produced by
            :func:`compress_batch_mcus`.
        transform: inverse transform (the decoder's; "exact" for a
            standards-compliant decode).
        quality: JPEG quality factor; must match the encoder's.
        cordic_config: CORDIC config (``transform == "cordic"`` only).

    Returns:
        (B, 16mh, 16mw, 3) uint8 RGB reconstruction.
    """
    b, mh, mw = z.shape[:3]
    blocks = z[..., jnp.asarray(scan.inverse_zigzag_perm())]
    blocks = blocks.reshape(b, mh, mw, 6, 8, 8)
    yb = blocks[:, :, :, :4].reshape(b, mh, mw, 2, 2, 8, 8)
    yb = yb.transpose(0, 1, 3, 2, 4, 5, 6).reshape(b, 2 * mh, 2 * mw, 8, 8)
    ql = quant.qtable(quality)
    qc = quant.qtable(quality, chroma=True)
    y = _plane_inverse(quant.dequantize(yb, ql), transform,
                       cordic_config) + 128.0
    cb = _upsample(_plane_inverse(quant.dequantize(blocks[:, :, :, 4], qc),
                                  transform, cordic_config))
    cr = _upsample(_plane_inverse(quant.dequantize(blocks[:, :, :, 5], qc),
                                  transform, cordic_config))
    rgb = jnp.stack([y + _CR_TO_R * cr,
                     y - _CB_TO_G * cb - _CR_TO_G * cr,
                     y + _CB_TO_B * cb], axis=-1)
    return jnp.clip(jnp.round(rgb), 0.0, 255.0).astype(jnp.uint8)


_compress_jit = functools.partial(
    jax.jit, static_argnames=("transform", "quality", "cordic_config"))(
        compress_batch_mcus)

_decompress_jit = functools.partial(
    jax.jit, static_argnames=("transform", "quality", "cordic_config"))(
        decompress_batch_mcus)


def compress(img, quality: int = 50, transform: codec.Transform = "exact",
             cordic_config: cordic.CordicConfig = cordic.PAPER_CONFIG
             ) -> np.ndarray:
    """One (H, W, 3) RGB image -> its (mh*mw*6, 64) interleaved zig-zag
    levels (a batch of one through :func:`compress_batch_mcus`)."""
    rgb = pad_to_mcu(jnp.asarray(img))
    z = _compress_jit(rgb[None], transform, quality, cordic_config)[0]
    return np.asarray(z).reshape(-1, 64)


def decompress(z, height: int, width: int, quality: int,
               transform: codec.Transform = "exact",
               cordic_config: cordic.CordicConfig = cordic.PAPER_CONFIG
               ) -> jnp.ndarray:
    """(mh*mw*6, 64) interleaved levels -> (height, width, 3) uint8 RGB."""
    mh, mw = mcu_grid(height, width)
    z = jnp.asarray(z).reshape(1, mh, mw, BLOCKS_PER_MCU, 64)
    return _decompress_jit(z, transform, quality, cordic_config)[
        0, :height, :width]


def encode_image(img, quality: int = 50, transform: str = "exact",
                 cordic_config=None, *, tables: str = "auto") -> bytes:
    """Compress one (H, W, 3) RGB image to a ``DCTZ`` version-3 stream."""
    from repro.core import entropy
    img = jnp.asarray(img)
    h, w = img.shape[:2]
    z = compress(img, quality, transform,
                 cordic_config or cordic.PAPER_CONFIG)
    return entropy.encode_colour_zigzag_host(z, quality, transform, (h, w),
                                             tables=tables)
