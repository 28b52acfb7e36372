"""Jitted public wrappers for the cordic_loeffler Pallas kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cordic
from repro.kernels import common, tuning
from repro.kernels.cordic_loeffler import kernel


def _run(img: jnp.ndarray, config: cordic.CordicConfig, inverse: bool,
         tile: int | None, interpret: bool | None) -> jnp.ndarray:
    if interpret is None:
        interpret = common.interpret_default()
    h, w = img.shape[-2:]
    padded = common.pad2d_to_multiple(img, 8, 8)
    ph, pw = padded.shape[-2:]
    if tile is None:
        tile = tuning.tile_for("cordic_loeffler", max(ph, pw))
    th, tw = common.tile_shape(ph, pw, tile)

    fn = lambda x: kernel.cordic_loeffler_pallas(
        x, tile_h=th, tile_w=tw, config=config, inverse=inverse,
        interpret=interpret)
    for _ in range(img.ndim - 2):
        fn = jax.vmap(fn)
    out = fn(padded)
    return out[..., :h, :w] if (ph, pw) != (h, w) else out


def cordic_loeffler_dct(img: jnp.ndarray, *,
                        config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                        tile: int | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Paper-faithful Cordic-Loeffler blockwise DCT.  (..., H, W).

    ``tile=None`` routes through the tuned-tile artifact
    (:func:`repro.kernels.tuning.tile_for`); an explicit tile pins it.
    """
    return _run(img, config, inverse=False, tile=tile, interpret=interpret)


def cordic_loeffler_idct(coeffs: jnp.ndarray, *,
                         config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                         tile: int | None = None,
                         interpret: bool | None = None) -> jnp.ndarray:
    """Paper-faithful Cordic-Loeffler blockwise inverse DCT."""
    return _run(coeffs, config, inverse=True, tile=tile, interpret=interpret)
