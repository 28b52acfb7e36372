"""Pallas TPU kernel: RLE-symbolise zig-zagged blocks on device.

Device-resident realisation of the entropy encoder's first host stage
(:func:`repro.core.entropy.dense.symbolize_dense`): the grid tiles the
block axis, and each program turns its ``tile_blocks`` zig-zag rows into
the dense per-block symbol layout of :mod:`repro.core.entropy.dense` —
(run, size) symbols, amplitude fields and per-block symbol counts —
plus the two 256-bin alphabet histograms the host needs for Huffman
table negotiation.  Everything per-row is fixed-shape arithmetic:

* **categories** — magnitude category (bit length) as a sum of 15
  threshold compares (the ops layer guarantees ``|level| < 2**15``, so
  no ``frexp`` is needed on device);
* **runs** — the previous-nonzero position is an exclusive running
  maximum; both it and the unit-count prefix sum are computed with
  log-step shift doubling over the 63 AC lanes (6 static steps);
* **slot scatter** — each ZRL/coded symbol lands in its dense slot via
  a one-hot compare-sum against the 64 slot indices (the same
  no-data-dependent-writes idiom as ``pack_bits``); untouched slots
  keep the zero init, which *is* the EOB encoding;
* **histograms** — per-alphabet one-hot compare-sums, accumulated
  across grid steps by revisiting a single (1, 256) output block
  (sequential TPU grid; ``@pl.when(i == 0)`` zeroes it first).

Row validity (the block count is rarely a tile multiple) comes in via
scalar prefetch; padded rows contribute nothing to histograms and get
``total == 0``.  Element-exact against ``dense.symbolize_dense`` by the
tile-invariance and ``--check-identical`` gates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

AC_LEN = 63
SLOTS = 64
MAX_ZRL = (AC_LEN - 1) // 16       # a run can skip at most 62 zeros
EOB = 0x00
ZRL = 0xF0
MAX_CATEGORY = 15


def _shift_right(x: jnp.ndarray, s: int, fill: int) -> jnp.ndarray:
    """Shift columns right by ``s``, filling vacated lanes with ``fill``."""
    t, _ = x.shape
    pad = jnp.full((t, s), fill, x.dtype)
    return jnp.concatenate([pad, x[:, :-s]], axis=1)


def _category(mag: jnp.ndarray) -> jnp.ndarray:
    """Bit length of a magnitude < 2**15 as 15 threshold compares."""
    cat = jnp.zeros_like(mag)
    for b in range(MAX_CATEGORY):
        cat += (mag >= (1 << b)).astype(mag.dtype)
    return cat


def _make_kernel(tile_blocks: int, n_classes: int):
    t = tile_blocks

    def kernel(nrows_ref, dc_ref, ac_ref, *refs):
        if n_classes > 1:
            cls = refs[0][...]                             # (t, 1) int32
            refs = refs[1:]
        (syms_ref, amps_ref, lens_ref, total_ref, dc_hist_ref,
         ac_hist_ref) = refs
        i = pl.program_id(0)
        row = (i * t + jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0))
        valid_row = row < nrows_ref[0]
        dcd = dc_ref[...]                                  # (t, 1) int32
        acb = ac_ref[...]                                  # (t, 63) int32

        dc_cat = _category(jnp.abs(dcd))
        dc_amp = jnp.where(dcd >= 0, dcd, dcd + (1 << dc_cat) - 1)

        nz = (acb != 0) & valid_row
        cat = _category(jnp.abs(acb))
        amp = jnp.where(acb >= 0, acb, acb + (1 << cat) - 1)

        # exclusive running max of nonzero positions = previous nonzero
        col = jax.lax.broadcasted_iota(jnp.int32, (t, AC_LEN), 1)
        run_max = jnp.where(nz, col, -1)
        for s in (1, 2, 4, 8, 16, 32):
            run_max = jnp.maximum(run_max, _shift_right(run_max, s, -1))
        prev = _shift_right(run_max, 1, -1)
        run = col - prev - 1
        zrl = run >> 4
        unit = jnp.where(nz, zrl + 1, 0)
        cu = unit
        for s in (1, 2, 4, 8, 16, 32):
            cu = cu + _shift_right(cu, s, 0)
        start = 1 + cu - unit
        coded_slot = start + zrl

        eob = ((run_max[:, -1:] != AC_LEN - 1) & valid_row)
        total = jnp.where(valid_row,
                          1 + cu[:, -1:] + eob.astype(jnp.int32), 0)

        # dense slot scatter: one-hot compare against the 64 slot
        # indices; inactive lanes target slot 64, which matches nothing.
        # Slots are unique per block, so each (row, slot) cell receives
        # at most one contribution and int32 sums are exact.
        slots3 = jax.lax.broadcasted_iota(jnp.int32, (t, AC_LEN, SLOTS), 2)
        coef_sym = ((run & 15) << 4) | cat

        def scatter(tgt, val):
            hit = (jnp.where(nz, tgt, SLOTS)[:, :, None] == slots3)
            return jnp.where(hit, val[:, :, None], 0).sum(axis=1)

        syms = scatter(coded_slot, coef_sym)
        amps = scatter(coded_slot, amp)
        lens = scatter(coded_slot, cat)
        for k in range(MAX_ZRL):
            live = nz & (zrl > k)
            hit = (jnp.where(live, start + k, SLOTS)[:, :, None] == slots3)
            syms += jnp.where(hit, ZRL, 0).sum(axis=1)

        slot2 = jax.lax.broadcasted_iota(jnp.int32, (t, SLOTS), 1)
        syms_ref[...] = syms + jnp.where(slot2 == 0, dc_cat, 0)
        amps_ref[...] = amps + jnp.where(slot2 == 0, dc_amp, 0)
        lens_ref[...] = lens + jnp.where(slot2 == 0, dc_cat, 0)
        total_ref[...] = total

        # per-alphabet histograms, accumulated across sequential grid
        # steps into one revisited (n_classes, 256) block
        bins = jax.lax.broadcasted_iota(jnp.int32, (1, 256), 1)

        def hist(rows, nzc, eobc):
            dc_sym_h = jnp.where(rows, dc_cat, -1)         # (t, 1)
            dc_step = (dc_sym_h == bins).astype(jnp.int32).sum(
                axis=0, keepdims=True)                     # (1, 256)
            ac_sym_h = jnp.where(nzc, coef_sym, -1)[:, :, None]
            ac_step = (ac_sym_h == bins[None]).astype(jnp.int32).sum(
                axis=1).sum(axis=0, keepdims=True)
            zrl_sum = jnp.where(nzc, zrl, 0).sum()
            eob_sum = eobc.astype(jnp.int32).sum()
            ac_step = (ac_step
                       + jnp.where(bins == ZRL, zrl_sum, 0)
                       + jnp.where(bins == EOB, eob_sum, 0))
            return dc_step, ac_step

        @pl.when(i == 0)
        def _init():
            dc_hist_ref[...] = jnp.zeros_like(dc_hist_ref)
            ac_hist_ref[...] = jnp.zeros_like(ac_hist_ref)

        if n_classes == 1:
            dc_step, ac_step = hist(valid_row, nz, eob)
            dc_hist_ref[...] += dc_step
            ac_hist_ref[...] += ac_step
        else:
            # one class at a time, each row accumulated before the next
            # class's one-hot is built
            for c in range(n_classes):
                dc_step, ac_step = hist(valid_row & (cls == c),
                                        nz & (cls == c), eob & (cls == c))
                dc_hist_ref[c:c + 1, :] += dc_step
                ac_hist_ref[c:c + 1, :] += ac_step

    return kernel


@functools.partial(jax.jit, static_argnames=("tile_blocks", "n_classes",
                                             "interpret"))
def symbolize_pallas(dc_diff: jnp.ndarray, ac: jnp.ndarray,
                     nrows: jnp.ndarray, cls: jnp.ndarray | None = None, *,
                     tile_blocks: int = 64, n_classes: int = 1,
                     interpret: bool = True) -> tuple:
    """Symbolise padded zig-zag blocks into dense slots + histograms.

    Args:
        dc_diff: (n_pad, 1) int32 DC differences; ``n_pad`` a multiple
            of ``tile_blocks``; ``|values| < 2**15`` (ops-layer guard).
        ac: (n_pad, 63) int32 AC tails in zig-zag order, same bound.
        nrows: (1,) int32 scalar-prefetch — the real block count; rows
            at and past it are padding (zero histogram weight,
            ``total == 0``).
        cls: (n_pad, 1) int32 table class of each block, in
            ``[0, n_classes)``; required when ``n_classes > 1``.
        tile_blocks: blocks per grid program.
        n_classes: table classes, one histogram row each.
        interpret: run in Pallas interpret mode (non-TPU backends).

    Returns:
        ``(syms, amp_vals, amp_lens, total, dc_hist, ac_hist)`` —
        (n_pad, 64) int32 dense slot arrays, (n_pad, 1) int32 per-block
        symbol counts, and two (n_classes, 256) int32 alphabet
        histograms.
    """
    n_pad = dc_diff.shape[0]
    if n_pad % tile_blocks:
        raise ValueError(f"{n_pad} rows not a multiple of tile_blocks="
                         f"{tile_blocks}")
    n_tiles = n_pad // tile_blocks
    t = tile_blocks
    tile = lambda i, nrows_ref: (i, 0)
    fixed = lambda i, nrows_ref: (0, 0)
    in_specs = [pl.BlockSpec((t, 1), tile), pl.BlockSpec((t, AC_LEN), tile)]
    args = [nrows, dc_diff, ac]
    if n_classes > 1:
        in_specs.append(pl.BlockSpec((t, 1), tile))
        args.append(cls)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((t, SLOTS), tile),
            pl.BlockSpec((t, SLOTS), tile),
            pl.BlockSpec((t, SLOTS), tile),
            pl.BlockSpec((t, 1), tile),
            pl.BlockSpec((n_classes, 256), fixed),
            pl.BlockSpec((n_classes, 256), fixed),
        ],
    )
    out_shape = [
        jax.ShapeDtypeStruct((n_pad, SLOTS), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, SLOTS), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, SLOTS), jnp.int32),
        jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
        jax.ShapeDtypeStruct((n_classes, 256), jnp.int32),
        jax.ShapeDtypeStruct((n_classes, 256), jnp.int32),
    ]
    return pl.pallas_call(
        _make_kernel(tile_blocks, n_classes),
        out_shape=out_shape,
        grid_spec=grid_spec,
        interpret=interpret,
    )(*args)
