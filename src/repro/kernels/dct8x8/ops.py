"""Jitted public wrappers for the dct8x8 Pallas kernel.

Handles padding to tile multiples, leading batch dims (vmap), and
interpret-mode selection (CPU container: interpret=True; real TPU:
compiled).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import common, tuning
from repro.kernels.dct8x8 import kernel


def _run(img: jnp.ndarray, inverse: bool, tile: int | None,
         interpret: bool | None) -> jnp.ndarray:
    if interpret is None:
        interpret = common.interpret_default()
    h, w = img.shape[-2:]
    padded = common.pad2d_to_multiple(img, 8, 8)
    ph, pw = padded.shape[-2:]
    if tile is None:
        tile = tuning.tile_for("dct8x8", max(ph, pw))
    th, tw = common.tile_shape(ph, pw, tile)

    fn = lambda x: kernel.dct8x8_pallas(x, tile_h=th, tile_w=tw,
                                        inverse=inverse, interpret=interpret)
    for _ in range(img.ndim - 2):
        fn = jax.vmap(fn)
    out = fn(padded)
    return out[..., :h, :w] if (ph, pw) != (h, w) else out


def dct8x8(img: jnp.ndarray, *, tile: int | None = None,
           interpret: bool | None = None) -> jnp.ndarray:
    """Blockwise 8x8 2-D DCT, block-planar layout.  (..., H, W).

    ``tile=None`` routes through the tuned-tile artifact
    (:func:`repro.kernels.tuning.tile_for`); an explicit tile pins it.
    """
    return _run(img, inverse=False, tile=tile, interpret=interpret)


def idct8x8(coeffs: jnp.ndarray, *, tile: int | None = None,
            interpret: bool | None = None) -> jnp.ndarray:
    """Blockwise 8x8 2-D inverse DCT, block-planar layout.  (..., H, W)."""
    return _run(coeffs, inverse=True, tile=tile, interpret=interpret)
