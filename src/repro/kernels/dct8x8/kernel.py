"""Pallas TPU kernel: blockwise 8x8 2-D DCT / IDCT.

TPU adaptation of the paper's CUDA DCT kernel (DESIGN.md §2).  The CUDA
version assigns one thread block per 8x8 pixel block with shared-memory
staging; here each *grid cell* owns a (TH, TW) VMEM tile holding
(TH/8)·(TW/8) pixel blocks, and transforms all of them at once with the
separable 8-point DCT written over the tile's 8 row phases
(:func:`repro.kernels.common.blockwise_2d`): every output coefficient
is an 8-term multiply-add chain on the vector unit, so a block's result
is the same in every tile.

VMEM at the default 256x256 f32 tile: 256 KiB in + 256 KiB out, each
double-buffered, plus the per-phase temporaries of the transform; the
compile rehearsal in ``tests/test_tpu_compile.py`` checks the whole
kernel fits a v5e core.  Compiled for a v5e at 512x512,
``memory_analysis()`` gives 1 MiB of argument, 1 MiB of output and no
HBM temporaries.

Layout: both input and output use the *in-place block-planar* convention —
the coefficient block of image block (i, j) lives at pixels
[8i:8i+8, 8j:8j+8] (JPEG-style), so forward and inverse kernels compose
without reshuffles and the HBM access pattern is fully coalesced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import dct
from repro.kernels import common


def _make_kernel(inverse: bool):
    def kernel(x_ref, o_ref):
        x = x_ref[...].astype(jnp.float32)
        if inverse:
            out = common.blockwise_2d(x, dct.idct8_terms,
                                      vertical_first=True)
        else:
            out = common.blockwise_2d(x, dct.dct8_terms)
        o_ref[...] = out.astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("tile_h", "tile_w", "inverse",
                                             "interpret"))
def dct8x8_pallas(img: jnp.ndarray, *, tile_h: int, tile_w: int,
                  inverse: bool = False,
                  interpret: bool = True) -> jnp.ndarray:
    """Blockwise 2-D (I)DCT of a (H, W) image, block-planar layout.

    H % tile_h == 0 and W % tile_w == 0; tile_h a multiple of 8, tile_w
    a multiple of 128 or W (ops.py enforces via ``common.tile_shape``).
    """
    h, w = img.shape
    return pl.pallas_call(
        _make_kernel(inverse),
        out_shape=jax.ShapeDtypeStruct((h, w), img.dtype),
        grid=(h // tile_h, w // tile_w),
        in_specs=[pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
        interpret=interpret,
    )(img)
