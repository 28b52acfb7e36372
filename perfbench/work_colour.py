"""Work of the colour programs, counted from their shapes alone.

Per RGB pixel of a 4:2:0 image there are 1.5 component samples (a full
Y plane and two quarter-size chroma planes), so 1.5 quantised levels.

Encode (``_compress_sharded_colour``) must at least read each pixel's
three bytes and write its 1.5 levels as 16-bit integers (a level needs
up to 15 amplitude bits and a sign): 6 bytes per pixel. It computes the
JFIF conversion (three outputs of three multiplies and two adds, and
Y's level shift: 16 flops), the 2x2 chroma mean (two planes, three adds
and a multiply per four pixels: 2 flops), the separable 8-point DCT of
every sample (8 multiply-adds per output per axis: 32 flops a sample,
48 a pixel) and quantisation (divide and round: 3 flops a pixel).

Decode (``_decompress_sharded_colour``) reads the 1.5 levels (3 bytes)
and writes three bytes: 6 bytes per pixel. It computes dequantisation
(1.5 flops), the inverse DCT (48), Y's level shift (1), the h2v2
triangle filter for two full-size chroma planes (two passes of two
multiplies and an add: 12) and the JFIF inverse conversion (a multiply
and an add for R and for B, two of each for G: 8): 70.5 flops a pixel.

How the program implements either (int32 levels, padded batches, fused
or not) does not change these counts, so they are the least any
implementation moves and computes, and a share of the roofline built on
them cannot pass 100%.
"""

from __future__ import annotations

from perfbench.work import least_seconds  # noqa: F401  (re-exported)

ENCODE_FLOPS_PER_PIXEL = 16 + 2 + 48 + 3
DECODE_FLOPS_PER_PIXEL = 1.5 + 48 + 1 + 12 + 8
BYTES_PER_PIXEL = 3 + 1.5 * 2


def encode_work(pixels: float) -> tuple:
    """(flops, bytes) of encoding ``pixels`` RGB pixels."""
    return ENCODE_FLOPS_PER_PIXEL * pixels, BYTES_PER_PIXEL * pixels


def decode_work(pixels: float) -> tuple:
    """(flops, bytes) of decoding ``pixels`` RGB pixels."""
    return DECODE_FLOPS_PER_PIXEL * pixels, BYTES_PER_PIXEL * pixels
