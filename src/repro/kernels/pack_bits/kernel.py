"""Pallas TPU kernel: scatter-pack Huffman bit fields into bytes.

Device-resident realisation of the entropy encoder's last stage.  The
serial dependency of bit packing is the field offsets; those are a
prefix sum computed *outside* the kernel (Cloud et al.,
arXiv:1107.1525), so the kernel itself is a pure scatter: the grid
tiles the **output** bit space, and each program gathers the window of
fields that can touch its tile and accumulates their byte
contributions.

Two structural tricks keep the scatter TPU-shaped:

* **block-aligned window instead of scatter** — fields are sorted by
  start offset and every kept field is at least one bit wide, so the
  fields overlapping a ``tile_bits``-bit tile form a contiguous index
  run of at most ``tile_bits + 15`` fields.  The field arrays are one
  lane-dense ``(3, M)`` stack (codes, widths, starts) cut into
  ``window``-field blocks, ``window >= tile_bits + 16``; the run of
  tile ``i`` lies inside blocks ``k`` and ``k + 1``, where ``k`` comes
  in via scalar prefetch.  Those two blocks are ordinary pipelined
  BlockSpec inputs, so VMEM holds ``2 * window`` fields whatever ``M``
  is.
* **one-hot byte accumulation** — a field of width <= 16 starting at
  bit offset ``s`` spans at most 3 bytes; its 24-bit aligned window
  splits into 3 byte contributions.  Distinct fields never share a bit,
  so byte values are a plain *sum* of contributions (each < 256), and
  a field outside the tile contributes to no byte of it.  The sum runs
  as a ``(tile_bytes, 2 * window)`` one-hot compare of each field's
  byte index (lanes) against the tile's byte indices (sublanes) — no
  data-dependent writes anywhere.

Bytes past the payload end are written as zero; the caller applies the
writer's 1-padding to the final partial byte (a framing concern, kept
at the edge).  Bit-exact against :mod:`repro.kernels.pack_bits.ref`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _make_kernel(tile_bits: int):
    nb = tile_bits // 8

    def kernel(blk_ref, lo_ref, hi_ref, out_ref):
        i = pl.program_id(0)
        f = jnp.concatenate([lo_ref[...], hi_ref[...]], axis=1)  # (3, 2W)
        c = f[0:1]
        ln = f[1:2]
        s = f[2:3] - i * tile_bits
        # byte-aligned 24-bit window of each field: bits occupy
        # [s, s+len) == bits [8b + r, 8b + r + len) with r in 0..7, so
        # v = code << (24 - r - len) places them inside bytes b..b+2
        b = s >> 3                       # floor(s / 8), also for s < 0
        r = s & 7
        v = jnp.where(ln > 0, c << (24 - r - ln), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (nb, 1), 0)
        acc = jnp.zeros((nb, f.shape[1]), jnp.int32)
        for t in range(3):
            byte_t = (v >> (16 - 8 * t)) & 0xFF
            acc += jnp.where(b + t == j, byte_t, 0)
        # fields never overlap in bit space, so summing the (at most
        # 8) sub-byte contributions per output byte is exact (< 256)
        col = acc.sum(axis=1, keepdims=True)                    # (nb, 1)
        # one small transpose turns the byte column into a lane-dense
        # row, so the output is not padded 128x in HBM
        out_ref[...] = jnp.transpose(jnp.broadcast_to(col, (nb, 128)))[0:1]

    return kernel


@functools.partial(jax.jit, static_argnames=("tile_bits", "window",
                                             "interpret"))
def pack_bits_pallas(fields: jnp.ndarray, blocks: jnp.ndarray, *,
                     tile_bits: int = 1024, window: int = 1152,
                     interpret: bool = True) -> jnp.ndarray:
    """Scatter-pack prepared bit fields into payload bytes.

    Args:
        fields: (3, M) int32 — rows are field values (low ``width`` bits
            used), widths in [0, 16] and start bit offsets (prefix sum
            of widths).  Kept fields (width > 0) come first, sorted by
            start; padding columns have width 0.  ``M`` is a multiple
            of ``window`` with at least two blocks.
        blocks: (n_tiles,) int32 scalar-prefetch — for each tile, the
            window block ``k`` holding the first field whose end
            exceeds the tile's start bit, clipped to ``M / window - 2``
            (see :mod:`.ops`).
        tile_bits: output bits per grid program (multiple of 64).
        window: fields per block; a multiple of 128 and at least
            ``tile_bits + 16`` so blocks ``k, k + 1`` hold every field
            that overlaps the tile.
        interpret: run in Pallas interpret mode (non-TPU backends).

    Returns:
        (n_tiles, 1, tile_bits // 8) int32 byte values in [0, 255];
        bytes past the payload end are zero.
    """
    if tile_bits % 64:
        raise ValueError(f"tile_bits {tile_bits} not a multiple of 64")
    if window < tile_bits + 16 or window % 128:
        raise ValueError(f"window {window} must be a multiple of 128 and "
                         f">= tile_bits + 16 to cover a {tile_bits}-bit "
                         f"tile")
    m = fields.shape[1]
    if m % window or m < 2 * window:
        raise ValueError(f"{m} fields is not a multiple of the {window}"
                         f"-field window with at least two blocks")
    n_tiles = blocks.shape[0]
    nb = tile_bits // 8
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((3, window), lambda i, blk: (0, blk[i])),
            pl.BlockSpec((3, window), lambda i, blk: (0, blk[i] + 1)),
        ],
        out_specs=pl.BlockSpec((None, 1, nb), lambda i, blk: (i, 0, 0)),
    )
    return pl.pallas_call(
        _make_kernel(tile_bits),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, nb), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(blocks, fields, fields)
