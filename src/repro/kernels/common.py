"""Shared helpers for the Pallas TPU kernels.

All kernels in this package target TPU (pl.pallas_call with explicit
BlockSpec VMEM tiling) and are *validated* on CPU via interpret mode, which
executes the kernel body in Python.  ``interpret_default()`` picks the mode
from the runtime backend so the same call sites work in both environments.

Tiles obey the TPU block rule: the last (lane) dimension of a block is a
multiple of 128 or the whole array dimension, the second-to-last
(sublane) dimension a multiple of 8 (:func:`tile_shape`).  Inside a
kernel, an 8x8-blockwise transform never reshapes a tile into blocks —
splitting the lane dimension is a relayout the TPU compiler refuses —
but reads the 8 row *phases* of the tile instead (:func:`blockwise_2d`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def interpret_default() -> bool:
    """True when we must interpret (no real TPU present)."""
    return jax.default_backend() != "tpu"


def pad2d_to_multiple(x: jnp.ndarray, mh: int, mw: int) -> jnp.ndarray:
    """Edge-pad the last two dims up to multiples of (mh, mw)."""
    h, w = x.shape[-2:]
    ph, pw = (-h) % mh, (-w) % mw
    if ph == 0 and pw == 0:
        return x
    pad = [(0, 0)] * (x.ndim - 2) + [(0, ph), (0, pw)]
    return jnp.pad(x, pad, mode="edge")


def pick_tile(dim: int, target: int = 256, multiple: int = 8) -> int:
    """Largest tile <= target that divides ``dim`` and is a multiple of 8.

    Image dims here are always positive multiples of 8 (ops pad first),
    so a valid tile always exists — worst case ``multiple`` itself,
    which is also the answer whenever ``target < multiple``: the tile
    must stay a multiple of ``multiple`` to keep whole 8x8 blocks per
    grid cell, so the target is a ceiling on the *search*, not on the
    returned tile.
    """
    if dim <= 0:
        raise ValueError(f"dim must be positive, got {dim}")
    if dim % multiple:
        raise ValueError(f"dim {dim} not a multiple of {multiple}")
    best = multiple
    t = multiple
    while t <= min(dim, target):
        if dim % t == 0:
            best = t
        t += multiple
    return best


LANES = 128


def pick_lane_tile(dim: int, target: int = 256) -> int:
    """Lane-dimension tile: a multiple of 128 dividing ``dim``, else ``dim``.

    The largest multiple of 128 that divides ``dim`` and is at most
    ``max(target, 128)``; when none divides (``dim`` = 480, 200, 104...)
    the whole dimension is one tile, the other shape the TPU accepts.
    """
    if dim <= 0:
        raise ValueError(f"dim must be positive, got {dim}")
    best = dim
    t = LANES
    while t <= min(dim, max(target, LANES)):
        if dim % t == 0:
            best = t
        t += LANES
    return best


def tile_shape(h: int, w: int, tile: int) -> tuple:
    """(tile_h, tile_w) for an (h, w) image with multiple-of-8 sides.

    The width tile follows :func:`pick_lane_tile`; the height tile then
    keeps the tile near ``tile * tile`` pixels (so a full-width tile of
    a wide image stays small enough for VMEM), a multiple of 8 dividing
    ``h`` (:func:`pick_tile`).
    """
    tw = pick_lane_tile(w, tile)
    th = pick_tile(h, max(8, tile * tile // tw))
    return th, tw


def _split_rows(x: jnp.ndarray) -> list:
    """(TH, TW) -> 8 arrays (TH/8, TW): phase ``i`` holds rows ``i::8``."""
    th, tw = x.shape
    x3 = x.reshape(th // 8, 8, tw)
    return [x3[:, i, :] for i in range(8)]


def _merge_rows(phases) -> jnp.ndarray:
    """Inverse of :func:`_split_rows`."""
    x3 = jnp.stack(phases, axis=1)
    return x3.reshape(-1, x3.shape[-1])


def blockwise_2d(x: jnp.ndarray, fn, *, vertical_first: bool = False
                 ) -> jnp.ndarray:
    """Apply an 8-point transform along both axes of every 8x8 block.

    ``fn`` maps a list of 8 equally-shaped arrays (sample phases) to the
    8 output phases (:func:`repro.core.dct.dct8_terms`,
    :func:`repro.core.loeffler.dct8_terms`...).  The horizontal pass
    transposes the tile so column phases become row phases; every step
    is elementwise per phase, so the result of a block never depends on
    the tile that holds it.  The forward 2-D transforms run the
    horizontal pass first and their inverses the vertical pass first,
    as :func:`repro.core.loeffler.loeffler_dct2d_8x8` does.
    """
    def vertical(t):
        return _merge_rows(fn(_split_rows(t)))

    def horizontal(t):
        return vertical(t.T).T

    if vertical_first:
        return horizontal(vertical(x))
    return vertical(horizontal(x))
