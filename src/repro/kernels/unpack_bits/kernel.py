"""Pallas TPU kernel: speculative Huffman decode of entropy payloads.

Device-resident realisation of the decode half of the entropy stage,
mirroring :mod:`repro.kernels.pack_bits.kernel` on the encode side.
Huffman decode is serial in the *bit offset* chain, not in the work:
following Cloud et al. (arXiv:1107.1525), the grid tiles the payload's
bit space and every program decodes **from every candidate bit offset**
of its tile at once, leaving only an O(1)-per-block chain resolution to
the host (:func:`repro.kernels.unpack_bits.ref.resolve`).

The work splits in two device stages:

* **unit words (Pallas)** — every bit offset of the payload is an
  independent lookup, laid out lane-dense as ``(rows, 128)`` offsets
  and tiled over rows.  Canonical bounds replace the 64K prefix LUT:
  the host hands in the tables' per-length ``(mincode, maxcode,
  valptr)`` triplets and symbol lists via scalar prefetch; a codeword
  is matched by 16 unrolled compares of the window's top ``L`` bits
  against the length-``L`` bounds (prefix-free codes make at most one
  length match, so matches combine with ``where`` and no priority
  logic), and the symbol comes from a loop over the 256 symbol slots.
* **chain outcomes (XLA)** — each offset's AC unit is summarised as
  ``next`` (first bit after the unit) and ``dpos`` (coefficient
  positions covered); six squarings via ``jnp.take_along_axis``
  collapse every speculative AC chain to its terminal or its
  position-63 crossing, exactly as the NumPy stage.  These gathers run
  across a whole tile window, which the TPU compiler does not lower
  inside a kernel, so they are a plain jitted XLA program on the
  staged tile windows.

Values stay in the bitstream: unit words carry control and advance
only; amplitudes are re-read on the host at resolved offsets, so
per-offset state is O(1) regardless of payload size.

Each tile covers ``tile_bits`` offsets plus a ``window - tile_bits``
overhang so any block *starting* in the tile finishes inside the
window (see ``ref.MARGIN_BITS``).  Unit and outcome words are
bit-identical to :mod:`repro.kernels.unpack_bits.ref` at every offset
the resolver can consume; margin-start chains clamped at the window
edge are never read back.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.unpack_bits import ref as _ref

_ZRL = _ref.ZRL

LANES = 128
ROWS = 16                   # offset rows per unit-word program (2048 bits)

# scalar-prefetch layout: nbits | DC bounds | AC bounds | DC syms | AC syms
_DC_BOUNDS, _AC_BOUNDS = 1, 49
_DC_SYMS, _AC_SYMS = 97, 353
N_PARAMS = 609


def _unit_words(w16, pidx, nbits, p_ref, bounds: int, syms: int):
    length = jnp.zeros(w16.shape, jnp.int32)
    sidx = jnp.zeros(w16.shape, jnp.int32)
    for L in range(1, 17):
        c = w16 >> (16 - L)
        mn = p_ref[bounds + L - 1]
        mx = p_ref[bounds + 16 + L - 1]
        vp = p_ref[bounds + 32 + L - 1]
        ok = (mx >= 0) & (c >= mn) & (c <= mx)
        length = jnp.where(ok, L, length)
        sidx = jnp.where(ok, vp + (c - mn), sidx)

    def lookup(k, sym):
        return jnp.where(sidx == k, p_ref[syms + k], sym)

    sym = jax.lax.fori_loop(0, 256, lookup, jnp.zeros(w16.shape, jnp.int32))
    sym = jnp.where(length > 0, sym, 0)
    size = jnp.where(sym > _ref.MAX_CATEGORY, sym & 0xF, sym)
    adv = length + size
    ctrl = jnp.where(length == 0, -1, sym)
    ctrl = jnp.where(pidx + adv > nbits, -2, ctrl)
    adv = jnp.where(ctrl < 0, 0, adv)
    return ((ctrl + 2) << 6) | adv


def _unit_kernel(p_ref, win_ref, dcw_ref, acw_ref):
    i = pl.program_id(0)
    shape = win_ref.shape
    w16 = win_ref[...]
    pidx = ((i * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
            * LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    nbits = p_ref[0]
    dcw_ref[...] = _unit_words(w16, pidx, nbits, p_ref, _DC_BOUNDS, _DC_SYMS)
    acw_ref[...] = _unit_words(w16, pidx, nbits, p_ref, _AC_BOUNDS, _AC_SYMS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unit_words_pallas(params: jnp.ndarray, win: jnp.ndarray, *,
                      interpret: bool = True) -> tuple:
    """DC and AC unit words for every payload bit offset.

    Args:
        params: (N_PARAMS,) int32 scalar-prefetch — the payload bit
            count, then per-length canonical bounds ``mincode[16] |
            maxcode[16] | valptr[16]`` for the DC and the AC table
            (``maxcode == -1`` marks an unused code length), then each
            table's 256-slot symbol list in canonical order.
        win: (rows, 128) int32 MSB-first 16-bit windows from
            ``bitio.bit_windows`` in offset order, padded with 0xFFFF;
            ``rows`` a multiple of :data:`ROWS`.
        interpret: run in Pallas interpret mode (non-TPU backends).

    Returns:
        ``(dc_words, ac_words)`` — (rows, 128) int32 unit words in the
        layout of :mod:`repro.kernels.unpack_bits.ref`.
    """
    rows = win.shape[0]
    if rows % ROWS or win.shape[1] != LANES:
        raise ValueError(f"windows {win.shape} are not ({ROWS}k, {LANES})")
    block = pl.BlockSpec((ROWS, LANES), lambda i, p: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // ROWS,),
        in_specs=[block],
        out_specs=[block, block],
    )
    shape = jax.ShapeDtypeStruct(win.shape, jnp.int32)
    return pl.pallas_call(
        _unit_kernel,
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        interpret=interpret,
    )(params, win)


def _gather(arr, idx):
    """``arr[t, idx[t]]`` per tile row."""
    return jnp.take_along_axis(arr, idx, axis=1)


@functools.partial(jax.jit, static_argnames=("n_tiles", "tile_bits",
                                             "window"))
def stage_tiles(dc_words: jnp.ndarray, ac_words: jnp.ndarray, *,
                n_tiles: int, tile_bits: int, window: int) -> tuple:
    """Cut flat unit words into tile windows and resolve AC outcomes.

    Args:
        dc_words, ac_words: flat int32 unit words covering at least
            ``n_tiles * tile_bits + window`` offsets.
        n_tiles: tiles to stage (static via the jit cache key).
        tile_bits: bit offsets resolved per tile.
        window: offsets staged per tile; must cover ``tile_bits +
            MARGIN_BITS`` so chains starting in the tile finish inside.

    Returns:
        ``(dc_words, ac_words, outcomes)`` — (n_tiles, window) int32
        arrays in the layouts documented in
        :mod:`repro.kernels.unpack_bits.ref`.
    """
    if window < tile_bits + _ref.MARGIN_BITS:
        raise ValueError(f"window {window} cannot cover a {tile_bits}-bit "
                         f"tile (needs >= tile_bits + {_ref.MARGIN_BITS})")
    t0 = jnp.arange(n_tiles, dtype=jnp.int32)[:, None] * tile_bits
    idx = jax.lax.broadcasted_iota(jnp.int32, (n_tiles, window), 1)
    dcw = dc_words[t0 + idx]
    acw = ac_words[t0 + idx]

    ctrl = (acw >> 6) - 2
    adv = acw & 0x3F
    term = ctrl <= 0
    d0 = jnp.where(term, 0, (ctrl >> 4) + 1)
    j0 = jnp.where(term, idx, jnp.minimum(idx + adv, window - 1))
    levels = []
    J, S = j0, d0
    for _ in range(6):
        levels.append((J, S))
        S = S + _gather(S, J)
        J = _gather(J, J)
    t_ctrl = _gather(ctrl, J)
    t_end = t0 + J + _gather(adv, J)
    t_out = jnp.where(
        t_ctrl == 0, t_end << 2,
        jnp.where(t_ctrl == -1, ((t0 + J) << 2) | 1,
                  ((t0 + J) << 2) | 2))
    cur, s = idx, jnp.zeros((n_tiles, window), jnp.int32)
    for Jk, Sk in reversed(levels):
        ns = s + _gather(Sk, cur)
        take = ns < 63
        s = jnp.where(take, ns, s)
        cur = jnp.where(take, _gather(Jk, cur), cur)
    c_ctrl = _gather(ctrl, cur)
    c_run = jnp.where(c_ctrl > 0, c_ctrl >> 4, 0)
    overrun = (c_ctrl != _ZRL) & (s + c_run + 1 >= 64)
    c_out = jnp.where(overrun, 3, (t0 + cur + _gather(adv, cur)) << 2)
    return dcw, acw, jnp.where(S < 63, t_out, c_out)
