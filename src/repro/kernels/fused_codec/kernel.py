"""Pallas TPU kernel: fused DCT -> quantise -> dequantise -> IDCT.

The paper runs DCT, quantiser and IDCT as *three separate CUDA kernels* —
three HBM round-trips.  At 8-bit-image arithmetic intensity the op is
bandwidth-bound on TPU v5e (819 GB/s HBM vs 197 TFLOP/s), so fusing the
whole codec into one kernel cuts HBM traffic ~3x: the tile is read once,
transformed, quantised, reconstructed in VMEM, and written once (plus the
quantised coefficients as a second output for entropy coding / telemetry).

Both transforms run on the tile's 8 row phases
(:func:`repro.kernels.common.blockwise_2d`): "exact" is the separable
8-point DCT of :func:`repro.core.dct.dct8_terms`, "cordic" the
Loeffler graph with CORDIC rotations of
:func:`repro.core.loeffler.dct8_terms` — the very operations the staged
codec runs, so "cordic" levels match it exactly.

This is the main beyond-paper kernel-level optimisation (DESIGN.md §2);
benchmarks/bench_table1 reports unfused vs fused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cordic, dct, loeffler
from repro.kernels import common


def _transforms(transform: str, config: cordic.CordicConfig):
    """(forward, inverse) 8-point transforms over lists of phases."""
    if transform == "exact":
        return dct.dct8_terms, dct.idct8_terms
    if transform == "cordic":
        rot = cordic.make_cordic_rotate(config)
        qfn = cordic.fixed_quantizer(config)
        return (lambda xs: loeffler.dct8_terms(xs, rot, qfn),
                lambda ys: loeffler.idct8_terms(ys, rot, qfn))
    raise ValueError(f"unknown transform {transform!r}")


def _make_kernel(transform: str, config: cordic.CordicConfig):
    """transform: 'exact' (separable DCT) or 'cordic' (flow graph)."""
    fwd, inv = _transforms(transform, config)

    def kernel(x_ref, q_ref, rec_ref, qc_ref):
        x = x_ref[...].astype(jnp.float32) - 128.0  # JPEG level shift
        q = q_ref[...]             # quant step of every tile position
        coef = common.blockwise_2d(x, fwd)
        qc = jnp.round(coef / q)                     # quantise
        rec = common.blockwise_2d(qc * q, inv, vertical_first=True)
        rec_ref[...] = jnp.clip(jnp.round(rec + 128.0), 0.0, 255.0)
        qc_ref[...] = qc.astype(jnp.int32)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile_h", "tile_w", "transform",
                                             "config", "interpret"))
def fused_codec_pallas(img: jnp.ndarray, qtile: jnp.ndarray, *,
                       tile_h: int, tile_w: int, transform: str = "exact",
                       config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                       interpret: bool = True):
    """One-pass codec roundtrip of a (H, W) image.

    ``qtile`` is the (tile_h, tile_w) f32 quant-step table tiled over
    the tile's 8x8 blocks.  Returns (reconstructed f32 in [0,255],
    quantised coeffs int32 block-planar).
    """
    h, w = img.shape
    tile = pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j))
    rec, qc = pl.pallas_call(
        _make_kernel(transform, config),
        out_shape=(jax.ShapeDtypeStruct((h, w), jnp.float32),
                   jax.ShapeDtypeStruct((h, w), jnp.int32)),
        grid=(h // tile_h, w // tile_w),
        in_specs=[tile, pl.BlockSpec((tile_h, tile_w), lambda i, j: (0, 0))],
        out_specs=(tile, tile),
        interpret=interpret,
    )(img, qtile)
    return rec, qc
