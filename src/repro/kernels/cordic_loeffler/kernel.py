"""Pallas TPU kernel: paper-faithful Cordic-based Loeffler blockwise 2-D DCT.

The kernel body runs the Loeffler flow graph (4 serial stages, parallel
inside each stage — exactly the structure the paper describes) with CORDIC
micro-rotations, vectorised across all blocks of the VMEM tile: the
"parallel inside a stage" dimension maps to VPU lanes (the graph runs on
the tile's 8 row phases, :func:`repro.kernels.common.blockwise_2d`), and
every shift-add micro-rotation is a multiply-add by a power-of-two
constant.

This is the TPU-native rendering of the paper's CUDA kernel.  It is kept as
the paper-faithful *baseline*; the exact-transform kernels (dct8x8 /
fused_codec) are the beyond-paper path — see DESIGN.md §2 for why the
CORDIC trade inverts on TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import cordic, loeffler
from repro.kernels import common


def _make_kernel(config: cordic.CordicConfig, inverse: bool):
    rot = cordic.make_cordic_rotate(config)
    qfn = cordic.fixed_quantizer(config)

    def fwd(xs):
        return loeffler.dct8_terms(xs, rot, qfn)

    def inv(ys):
        return loeffler.idct8_terms(ys, rot, qfn)

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        if inverse:
            out = common.blockwise_2d(x, inv, vertical_first=True)
        else:
            out = common.blockwise_2d(x, fwd)
        o_ref[...] = out

    return kernel


@functools.partial(jax.jit, static_argnames=("tile_h", "tile_w", "config",
                                             "inverse", "interpret"))
def cordic_loeffler_pallas(img: jnp.ndarray, *, tile_h: int, tile_w: int,
                           config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                           inverse: bool = False,
                           interpret: bool = True) -> jnp.ndarray:
    """Blockwise Cordic-Loeffler 2-D (I)DCT, block-planar layout."""
    h, w = img.shape
    return pl.pallas_call(
        _make_kernel(config, inverse),
        out_shape=jax.ShapeDtypeStruct((h, w), img.dtype),
        grid=(h // tile_h, w // tile_w),
        in_specs=[pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
        interpret=interpret,
    )(img)
