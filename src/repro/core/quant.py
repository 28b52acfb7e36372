"""JPEG-style quantisation for 8x8 DCT coefficient blocks.

The paper's pipeline is DCT -> quantiser -> IDCT (each a separate CUDA
kernel).  We use the ITU-T T.81 Annex K luminance table (and, for the
chroma planes of colour images, its chrominance table) with the standard
IJG quality scaling.  Note: the orthonormal 2-D DCT used throughout this
repo coincides exactly with the JPEG FDCT convention (the (1/4)·C(u)C(v)
scaling equals the orthonormal alpha_u·alpha_v), so the table applies
without rescaling.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

# ITU-T T.81 Annex K, Table K.1 (luminance).
JPEG_LUMA_QTABLE = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99],
], dtype=np.float64)

# ITU-T T.81 Annex K, Table K.2 (chrominance): the Cb and Cr planes of a
# colour stream.
JPEG_CHROMA_QTABLE = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
], dtype=np.float64)


@functools.lru_cache(maxsize=None)
def _scaled_qtable_np(quality: int, chroma: bool = False) -> np.ndarray:
    """IJG quality scaling: quality in [1, 100]."""
    quality = int(np.clip(quality, 1, 100))
    if quality < 50:
        scale = 5000.0 / quality
    else:
        scale = 200.0 - 2.0 * quality
    base = JPEG_CHROMA_QTABLE if chroma else JPEG_LUMA_QTABLE
    q = np.floor((base * scale + 50.0) / 100.0)
    return np.clip(q, 1.0, 255.0)


def qtable(quality: int = 50, dtype=jnp.float32, *,
           chroma: bool = False) -> jnp.ndarray:
    """Quantisation step table for an IJG quality factor.

    This is the only table-derivation rule in the codec: the ``DCTZ``
    bitstream stores just the quality byte and decoders rebuild the
    steps with exactly this function (docs/bitstream.md §5).

    Args:
        quality: IJG quality factor, clipped to [1, 100]; 50 is the
            unscaled Annex K table, lower is coarser.
        dtype: element dtype of the returned table.
        chroma: scale Table K.2 (chrominance, the quantisation class
            of a colour stream's Cb and Cr) instead of K.1.

    Returns:
        (8, 8) array of quantisation steps in [1, 255].
    """
    return jnp.asarray(_scaled_qtable_np(quality, chroma), dtype=dtype)


def quantize(coeffs: jnp.ndarray, q: jnp.ndarray) -> jnp.ndarray:
    """Round DCT coefficients to quantised levels.

    Args:
        coeffs: (..., 8, 8) float DCT coefficients (any leading batch/
            block-grid axes).
        q: (8, 8) step table from :func:`qtable` (broadcast over the
            leading axes).

    Returns:
        (..., 8, 8) int32 quantised levels ``round(coeffs / q)``.
    """
    return jnp.round(coeffs / q).astype(jnp.int32)


def dequantize(qcoeffs: jnp.ndarray, q: jnp.ndarray,
               dtype=jnp.float32) -> jnp.ndarray:
    """Reconstruct coefficient values from quantised levels.

    Args:
        qcoeffs: (..., 8, 8) int quantised levels from :func:`quantize`.
        q: (8, 8) step table; must match the quantiser's.
        dtype: output dtype.

    Returns:
        (..., 8, 8) dequantised coefficients ``qcoeffs * q``.
    """
    return qcoeffs.astype(dtype) * q.astype(dtype)


@functools.lru_cache(maxsize=None)
def _zigzag_perm(n: int = 8) -> np.ndarray:
    """Raster->zigzag permutation of block indices (length n*n)."""
    idx = sorted(((i + j, i if (i + j) % 2 else j, i, j)
                  for i in range(n) for j in range(n)))
    return np.array([i * n + j for (_, _, i, j) in idx], dtype=np.int32)


def zigzag(blocks: jnp.ndarray) -> jnp.ndarray:
    """Reorder blocks into the JPEG zig-zag sequence.

    Args:
        blocks: (..., n, n) square blocks (n = 8 in the codec).

    Returns:
        (..., n*n) array in zig-zag order (DC first); the inverse lives
        in :mod:`repro.core.entropy.scan` (``zigzag_unscan``).
    """
    *lead, b, b2 = blocks.shape
    perm = jnp.asarray(_zigzag_perm(b))
    return blocks.reshape(*lead, b * b2)[..., perm]


def estimate_bits(qcoeffs: jnp.ndarray) -> jnp.ndarray:
    """JPEG-flavoured size *proxy* (bits) for quantised blocks.

    The **one** surviving device-side size estimator (the PR 5 audit
    deleted every other proxy — ``CompressedImage.nbytes_estimate``,
    ``quant.compression_ratio`` — in favour of measured stream bytes).
    It stays because it is jit-able inside compiled pipelines, where
    bit packing is not: ``CompressedBatch.nbytes_estimate`` uses it for
    pre-materialisation telemetry, and that is its only load-bearing
    call site.  Every *reported* size in RESULTS.md is a measured
    entropy-coded stream length (``CompressedImage.nbytes`` /
    :mod:`repro.core.entropy`), never this.

    Per nonzero coefficient: magnitude-category bits + ~4 bits of
    Huffman overhead; + 4 bits EOB per block.

    Args:
        qcoeffs: (..., 8, 8) int quantised levels.

    Returns:
        Scalar estimated bit count over all blocks.
    """
    mag = jnp.abs(qcoeffs).astype(jnp.float32)
    nz = mag > 0
    cat_bits = jnp.where(nz, jnp.ceil(jnp.log2(mag + 1.0)), 0.0)
    huff_bits = jnp.where(nz, 4.0, 0.0)
    per_block = (cat_bits + huff_bits).sum(axis=(-1, -2)) + 4.0
    return per_block.sum()
