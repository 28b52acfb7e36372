"""Work of one call, counted from its shapes, and its least time on a chip.

The fused roundtrip of a (B, H, W) uint8 batch must at least read every
pixel once and write its reconstruction once (2 bytes per pixel), and
compute the separable 8-point matrix form of the forward and inverse
DCT (8 multiply-adds per output, per axis, per direction: 64 flops per
pixel) plus quantisation and dequantisation (divide, round, multiply: 3
flops per pixel). How the program implements it does not change these
counts.
"""

from __future__ import annotations

import math

ROUNDTRIP_FLOPS_PER_PIXEL = 2 * (2 * 8 * 2) + 3
ROUNDTRIP_BYTES_PER_PIXEL = 2


def roundtrip_work(shape) -> tuple:
    """(flops, bytes) of one roundtrip call on a batch of ``shape``."""
    px = math.prod(shape)
    return ROUNDTRIP_FLOPS_PER_PIXEL * px, ROUNDTRIP_BYTES_PER_PIXEL * px


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound): the roofline's least time and what sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("bytes" if t_bytes >= t_flops
                                   else "flops")
