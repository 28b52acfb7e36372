"""Pallas TPU kernel and XLA walk: speculative Huffman decode.

Device-resident realisation of the decode half of the entropy stage,
mirroring :mod:`repro.kernels.pack_bits.kernel` on the encode side.
Huffman decode is serial in the *bit offset* chain, not in the work:
following Cloud et al. (arXiv:1107.1525), every program decodes **from
every candidate bit offset** at once, leaving only an O(1)-per-block
chain resolution to the host (:func:`repro.kernels.unpack_bits.ref.resolve`).

The work splits in two device stages, both over the whole payload:

* **unit words** (``unit_words_pallas``, Pallas) — every bit offset
  of the payload is an independent lookup, laid out lane-dense as
  ``(rows, 128)`` offsets and tiled over rows.  Canonical bounds
  replace the 64K prefix LUT: the host hands in the tables' per-length
  ``(mincode, maxcode, valptr)`` triplets and symbol lists via scalar
  prefetch; a codeword is matched by 16 unrolled compares of the
  window's top ``L`` bits against the length-``L`` bounds (prefix-free
  codes make at most one length match, so matches combine with
  ``where`` and no priority logic), and the symbol comes from a loop
  over the 256 symbol slots.
* **chain outcomes** (``stage_tiles``, XLA) — a bounded forward walk.
  Each offset carries one walk word: where its AC chain stands, and
  how many coefficient positions lie behind it.  A step moves every
  chain one unit on by reading the word at ``offset + hop`` (``hop <=
  31``, the longest unit), a select over 31 static shifts with no
  gather; a chain stops on a terminal or on the unit that reaches
  position 63.  After 64 steps (more units than a block holds) the
  word gives the same outcome as the NumPy stage's pointer doubling
  and descent.  It is a ``fori_loop`` of 64 fused element-wise steps
  over the flat payload; a Pallas form of the same walk, blocked in
  VMEM, measured twice as slow on a v5e.

Values stay in the bitstream: unit words carry control and advance
only; amplitudes are re-read on the host at resolved offsets, so
per-offset state is O(1) regardless of payload size.  Unit and outcome
words are bit-identical to :mod:`repro.kernels.unpack_bits.ref` at
every offset up to the payload's bit count.
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


# Walk word: one int32 per offset carrying a chain's state,
#   bits 21..31  rel   offset of the chain's current unit, less the start
#   bits 14..20  pos   coefficient positions covered before that unit
#   bits  0..13  unit  that unit's word, ((ctrl + 2) << 5) | adv
# A step adds the start unit's own hop and positions to the word read at
# its end; rel stays under 64 * MAX_ADV = 1984 < 2**11 and pos under
# 63 + 16 = 79 < 2**7, so no field carries into the next.
_REL_SHIFT, _POS_SHIFT, _POS_MASK = 21, 14, 0x7F
MAX_ADV = 16 + _ref.MAX_CATEGORY      # 16-bit code + 15-bit amplitude
STEPS = 64                            # AC units a block may hold


def _outcome(y, pos):
    """Outcome words (``ref`` layout) from final walk words at ``pos``."""
    p = pos + jax.lax.shift_right_logical(y, _REL_SHIFT)
    s = (y >> _POS_SHIFT) & _POS_MASK
    ctrl = ((y >> 5) & 0x1FF) - 2
    end = (p + (y & 0x1F)) << 2
    # a ZRL may overshoot 63 freely; a coefficient landing past the
    # last column (position 62) is the reference's "overruns block"
    overrun = (ctrl > 0) & (ctrl != _ZRL) & (s + (ctrl >> 4) + 1 >= 64)
    return jnp.where(ctrl == -1, (p << 2) | 1,
                     jnp.where(ctrl == -2, (p << 2) | 2,
                               jnp.where(overrun, 3, end)))


@jax.jit
def stage_tiles(dc_words: jnp.ndarray, ac_words: jnp.ndarray) -> tuple:
    """Resolve the AC chain outcome of every payload bit offset.

    A bounded forward walk over the whole payload: 64 steps, each of
    which moves every offset's chain one unit on by reading the walk
    word ``hop <= MAX_ADV`` offsets ahead (a select over 31 static
    shifts, no gather), and keeps the move only while fewer than 63
    positions lie behind the chain's unit.  A chain so stops on a
    terminal or on the unit that reaches position 63, and the outcome
    is read off that unit.

    Args:
        dc_words, ac_words: unit words of ``unit_words_pallas`` for
            ``n >= nbits + 1 + MAX_ADV`` offsets (any shape; read
            flat).  Offsets past ``nbits`` must be terminal, as the
            unit-word kernel makes them.

    Returns:
        ``(dc_words, ac_words, outcomes)`` — flat ``(n,)`` int32 in the
        layouts of :mod:`repro.kernels.unpack_bits.ref`; outcomes equal
        ``ref._ac_outcomes`` over the whole payload at every offset up
        to ``nbits``.
    """
    acw = ac_words.reshape(-1)
    n = acw.shape[0]
    ctrl = (acw >> 6) - 2
    adv = acw & 0x3F
    term = ctrl <= 0                  # EOB, invalid, truncated: absorb
    hop = jnp.where(term, 0, adv)
    inc = ((hop << _REL_SHIFT)
           + (jnp.where(term, 0, (ctrl >> 4) + 1) << _POS_SHIFT))

    def step(_, y):
        ahead = jnp.concatenate([y, jnp.zeros(MAX_ADV, jnp.int32)])
        at = y
        for k in range(1, MAX_ADV + 1):
            at = jnp.where(hop == k, ahead[k:k + n], at)
        cand = at + inc
        return jnp.where(((cand >> _POS_SHIFT) & _POS_MASK) < 63, cand, y)

    y = jax.lax.fori_loop(0, STEPS, step, ((ctrl + 2) << 5) | adv)
    return (dc_words.reshape(-1), acw,
            _outcome(y, jnp.arange(n, dtype=jnp.int32)))
