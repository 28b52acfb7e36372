"""Jitted public wrappers for the fused_codec Pallas kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import cordic, quant
from repro.kernels import common, tuning
from repro.kernels.fused_codec import kernel


def fused_codec(img: jnp.ndarray, *, quality: int = 50,
                transform: str = "exact",
                config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                tile: int | None = None, interpret: bool | None = None):
    """One-pass codec roundtrip.  (..., H, W) uint8/float.

    Returns (reconstructed uint8, quantised coeffs int32 block-planar).
    ``tile=None`` routes through the tuned-tile artifact
    (:func:`repro.kernels.tuning.tile_for`); an explicit tile pins it.
    """
    if interpret is None:
        interpret = common.interpret_default()
    img = jnp.asarray(img)
    h, w = img.shape[-2:]
    padded = common.pad2d_to_multiple(img, 8, 8).astype(jnp.float32)
    ph, pw = padded.shape[-2:]
    if tile is None:
        tile = tuning.tile_for("fused_codec", max(ph, pw))
    th, tw = common.tile_shape(ph, pw, tile)
    qtile = jnp.tile(quant.qtable(quality), (th // 8, tw // 8))

    fn = lambda x: kernel.fused_codec_pallas(
        x, qtile, tile_h=th, tile_w=tw, transform=transform, config=config,
        interpret=interpret)
    for _ in range(img.ndim - 2):
        fn = jax.vmap(fn)
    rec, qc = fn(padded)
    rec = rec[..., :h, :w].astype(jnp.uint8)
    qc = qc[..., :h, :w]
    return rec, qc
