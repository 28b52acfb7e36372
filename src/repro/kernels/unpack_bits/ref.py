"""Staged NumPy reference for speculative parallel Huffman decode.

The scalar oracle (:func:`repro.core.entropy.rle.decode_payload_reference`)
walks the payload one codeword at a time; its LUT-walk successor
(``decode_payload``) removes the per-*codeword* Python loop but still
serialises on the chain of bit offsets.  This module removes that
dependency too, following Cloud et al. (arXiv:1107.1525): decode
speculatively from *every* candidate bit offset, then resolve the one
true chain per block.

The work is split into two stages sharing a compact per-position "unit
word" encoding (also produced by the Pallas kernel in
:mod:`repro.kernels.unpack_bits.kernel`):

1. **stage** (data-parallel, per tile) — for every bit offset ``p`` in
   a tile, decode the single codeword starting at ``p`` against both
   Huffman tables and summarise it as a unit word; then collapse each
   speculative *AC chain* starting at ``p`` into one outcome word via
   pointer doubling over the per-position ``next`` array (6 squarings
   cover the at-most-64 units of a block).
2. **resolve** (per block) — hop block starts through the
   precomputed outcomes: each block costs O(1) lookups (one DC unit
   word + one AC chain outcome), after which coefficient values are
   emitted tile-by-tile with a vectorized wavefront over all blocks
   that start in the tile (every block advances one unit per step, at
   most 64 steps, regardless of block count).  :func:`resolve` is the
   host's form and the oracle of the device's
   (``kernel._resolve_kernel``, the engine's route on TPU); both stop at
   the first broken block and name it with :func:`chain_error`.

Unit word layout (int64 here, int32 in the kernel)::

    word = (ctrl + 2) << 6 | advance
    ctrl    = -2 truncated | -1 invalid prefix | symbol byte
    advance = code length + amplitude width (0 for terminal units)

Outcome word layout::

    word = value << 2 | kind
    kind  = 0 ok (value = first bit after the block's AC run)
            1 invalid prefix   (value = offending bit offset)
            2 truncated        (value = offending bit offset)
            3 AC run overruns the block (value unused)

Amplitude bits are *not* staged: they are re-read from the shared
``bitio.bit_windows`` array only at resolved offsets, so decoder
scratch is bounded by ``TILE_BITS + MARGIN_BITS`` positions however
long the payload is — unlike the LUT walk, whose tables grow with
every payload bit (see :func:`scratch_nbytes`).

Bit-exact against ``decode_payload_reference`` on every stream, with
the same error classes and messages on malformed input.

A stream with more than one table class (a colour stream: block ``k``
takes the tables of class ``classes[k % len(classes)]``, see
:mod:`repro.core.entropy.rle`) stages unit words and outcomes once per
class, and the resolver hops each block through its own class's words.
"""

from __future__ import annotations

import itertools

import numpy as np

from repro.core.entropy import bitio, huffman, rle

AC_LEN = 63                   # AC coefficients per 8x8 block
MAX_CATEGORY = 15             # largest magnitude category (amplitude width)
ZRL = 0xF0                    # sixteen-zeros AC run marker

#: Default bit offsets per resolver tile.  Any positive value is
#: correct; this one keeps per-tile scratch around a few MB while
#: amortising the staging cost over many blocks.
TILE_BITS = 1 << 15

#: Stage window overhang past the tile: a block whose *DC* codeword
#: starts inside the tile must finish inside ``tile + margin``.  Worst
#: case is 31 bits of DC unit (16-bit code + 15-bit amplitude), then 63
#: non-terminal AC units of 31 bits plus one terminal EOB code of up to
#: 16 bits: 31 + 63 * 31 + 16 = 2000 < 2048.
MARGIN_BITS = 1 << 11

_CTRL_SHIFT = 6
_ADV_MASK = 0x3F

# outcome kinds
_OK, _INVALID, _TRUNCATED, _OVERRUN = 0, 1, 2, 3

# what stops a block chain (the device resolver's error record, too); an
# AC kind is its outcome kind plus 2
ERR_DC_INVALID, ERR_DC_TRUNCATED = 1, 2
ERR_AC_INVALID, ERR_AC_TRUNCATED, ERR_OVERRUN = 3, 4, 5


def chain_error(kind: int, block: int, bit: int, nbits: int) -> Exception:
    """The error ``rle.decode_payload`` raises when block ``block``'s
    chain stops with ``kind`` at bit offset ``bit``."""
    if kind in (ERR_DC_TRUNCATED, ERR_AC_TRUNCATED):
        return bitio.TruncatedStream(
            f"entropy payload truncated: needed bit {bit} of {nbits}")
    if kind == ERR_OVERRUN:
        return ValueError(f"corrupted stream: AC run overruns block {block}")
    which = "DC" if kind == ERR_DC_INVALID else "AC"
    return ValueError(f"invalid {which} Huffman prefix at bit {bit}")


def scratch_nbytes(nbits: int, tile_bits: int = TILE_BITS) -> int:
    """Upper bound on the staged decoder's per-tile scratch, in bytes.

    Counts the dominant int64 per-position arrays held at once while
    staging one tile: two unit-word arrays, the outcome array, the six
    doubling levels (position + step-sum each), and roughly four
    temporaries of the same shape.  The bound is *constant* in the
    payload size once ``nbits`` exceeds one tile — the claim the
    ``entropy_decode`` bench case measures against the LUT walk's
    per-payload-bit tables.
    """
    w = min(tile_bits + MARGIN_BITS, max(nbits, 0) + 1)
    return (3 + 12 + 4) * 8 * w


def _unit_words(win: np.ndarray, nbits: int, t0: int, w: int,
                sym_lut: np.ndarray, len_lut: np.ndarray) -> np.ndarray:
    """Speculative unit words for bit offsets ``[t0, t0 + w)``.

    One vectorized pass over the 16-bit windows: prefix-LUT decode,
    then classification.  Truncation (the codeword or its amplitude
    would read past ``nbits``) takes precedence over an invalid prefix,
    matching ``rle._decode_table``'s sentinel override.
    """
    hi = min(t0 + w, win.shape[0])
    ww = np.empty(w, np.int64)
    k = max(hi - t0, 0)
    ww[:k] = win[t0:hi]
    ww[k:] = 0xFFFF                        # past-end: arbitrary, truncated
    sym = sym_lut[ww].astype(np.int64)
    length = len_lut[ww].astype(np.int64)
    size = np.where(sym > MAX_CATEGORY, sym & 0xF, sym)
    adv = length + size
    ctrl = np.where(length == 0, -1, sym)
    ctrl = np.where(t0 + np.arange(w) + adv > nbits, -2, ctrl)
    adv = np.where(ctrl < 0, 0, adv)       # terminal units advance nowhere
    return ((ctrl + 2) << _CTRL_SHIFT) | adv


def _ac_outcomes(ac_words: np.ndarray, t0: int) -> np.ndarray:
    """Collapse every speculative AC chain into one outcome word.

    ``next`` hops land on the first bit after each unit; terminal units
    (EOB / invalid / truncated) absorb.  Each non-terminal unit covers
    ``run + 1`` coefficient positions (ZRL is run 15 with no
    coefficient, i.e. exactly 16 positions), so six squarings of the
    (position-after, positions-covered) maps summarise 64 units — more
    than any legal chain.  A chain either parks on a terminal with
    fewer than 63 positions covered, or crosses position 63; the
    crossing unit is recovered by a top-down binary descent through the
    saved doubling levels.
    """
    w = ac_words.shape[0]
    ctrl = (ac_words >> _CTRL_SHIFT) - 2
    adv = ac_words & _ADV_MASK
    idx = np.arange(w, dtype=np.int64)
    term = ctrl <= 0                       # EOB or error: absorbing
    d0 = np.where(term, 0, (ctrl >> 4) + 1)
    j0 = np.where(term, idx, np.minimum(idx + adv, w - 1))
    levels = []
    J, S = j0, d0
    for _ in range(6):
        levels.append((J, S))
        S = S + S[J]
        J = J[J]
    # parked-on-terminal branch (S < 63 after 64 steps)
    t_ctrl = ctrl[J]
    t_end = t0 + J + adv[J]
    t_out = np.where(
        t_ctrl == 0, (t_end << 2) | _OK,
        np.where(t_ctrl == -1, ((t0 + J) << 2) | _INVALID,
                 ((t0 + J) << 2) | _TRUNCATED))
    # crossing branch: descend to the unit that reaches position >= 63
    cur, s = idx.copy(), np.zeros(w, np.int64)
    for Jk, Sk in reversed(levels):
        ns = s + Sk[cur]
        take = ns < 63
        s = np.where(take, ns, s)
        cur = np.where(take, Jk[cur], cur)
    c_ctrl = ctrl[cur]
    c_run = np.where(c_ctrl > 0, c_ctrl >> 4, 0)
    # a ZRL may overshoot 63 freely; a coefficient landing past the
    # last column (position 62) is the reference's "overruns block"
    overrun = (c_ctrl != ZRL) & (s + c_run + 1 >= 64)
    c_out = np.where(overrun, _OVERRUN,
                     ((t0 + cur + adv[cur]) << 2) | _OK)
    return np.where(S < 63, t_out, c_out)


def _emit_tile(win: np.ndarray, t0: int, dc_words: list, ac_words: list,
               dc_starts: list, ac_starts: list, block_ids: list,
               dc_out: np.ndarray, ac_out: np.ndarray,
               classes: tuple) -> None:
    """Emit coefficient values for all blocks starting in one tile.

    DC amplitudes are gathered in one shot; AC units are emitted with a
    wavefront — every live block consumes one unit per step, so the
    loop runs at most 64 times however many blocks the tile holds.
    Amplitude bits are re-read from ``win`` at the resolved offsets
    only (the unit words carry no values).  ``dc_words``/``ac_words``
    hold one array per table class.
    """
    bids = np.asarray(block_ids, np.int64)
    cls = (None if len(dc_words) == 1
           else np.asarray(classes, np.int64)[bids % len(classes)])

    def amplitude(p, words, cls):
        if cls is None:
            x = words[0][p - t0]
        else:
            x = np.empty(p.shape, np.int64)
            for k, wk in enumerate(words):
                m = cls == k
                x[m] = wk[p[m] - t0]
        adv = x & _ADV_MASK
        c = (x >> _CTRL_SHIFT) - 2
        size = c & 0xF                     # c >= 0 for resolved units
        safe = np.maximum(size, 1)
        bits = win[p + (adv - size)].astype(np.int64) >> (16 - safe)
        val = np.where(bits < (1 << (safe - 1)), bits - (1 << safe) + 1,
                       bits)
        return c, adv, np.where(size == 0, 0, val)

    _, _, dc_val = amplitude(np.asarray(dc_starts, np.int64), dc_words, cls)
    dc_out[bids] = dc_val.astype(np.int32)

    pos = np.zeros(len(bids), np.int64)
    p = np.asarray(ac_starts, np.int64)
    alive = np.ones(len(bids), bool)
    while alive.any():
        c, adv, val = amplitude(p[alive], ac_words,
                                None if cls is None else cls[alive])
        eob = c == 0
        run = c >> 4
        coef = ~eob & (c != ZRL)
        col = pos[alive] + run
        if coef.any():
            ac_out[bids[alive][coef], col[coef]] = val[coef].astype(np.int32)
        new_pos = pos[alive] + np.where(eob, 0, run + 1)
        pos[alive] = new_pos
        p[alive] += adv
        live_idx = np.flatnonzero(alive)
        alive[live_idx[eob | (new_pos >= AC_LEN)]] = False


def resolve(win: np.ndarray, nbits: int, n_blocks: int, tile_bits: int,
            get_tile, classes: tuple = rle.ONE_CLASS) -> tuple:
    """Resolve the true chain and emit values from staged tiles.

    ``get_tile(t)`` must return ``(dc_words, ac_words, outcomes)`` for
    bit offsets ``[t * tile_bits, t * tile_bits + w)`` with
    ``w >= min(tile_bits + MARGIN_BITS, nbits + 1 - t * tile_bits)`` —
    the stage is the parallel part; this resolver is the serial O(1)
    -per-block remainder, on the host: the NumPy backend's, and the
    device stage's where the device resolver is not taken (an explicit
    ``tile_bits``, a stream over its guards).  It is also the oracle of
    the device resolver (``kernel._resolve_kernel``).
    With a ``classes`` pattern of more than one class, each of the three
    is a sequence of one array per class, and block ``b`` hops through
    the arrays of class ``classes[b % len(classes)]``.

    Raises exactly what ``rle.decode_payload`` raises, at the same bit
    offsets: :class:`repro.core.entropy.bitio.TruncatedStream` when a
    block needs bits past the payload, ``ValueError`` on invalid
    prefixes and AC runs overrunning a block.
    """
    dc_out = np.zeros(n_blocks, np.int32)
    ac_out = np.zeros((n_blocks, AC_LEN), np.int32)
    one = classes == rle.ONE_CLASS
    t = -1
    dcw = acw = outc = None
    dc_starts: list = []
    ac_starts: list = []
    block_ids: list = []
    p = 0
    for b, k in zip(range(n_blocks), itertools.cycle(classes)):
        nt = p // tile_bits
        if nt != t:
            if block_ids:
                _emit_tile(win, t * tile_bits, dcw, acw, dc_starts,
                           ac_starts, block_ids, dc_out, ac_out, classes)
                dc_starts, ac_starts, block_ids = [], [], []
            dcw, acw, outc = ([a] for a in get_tile(nt)) if one else \
                get_tile(nt)
            t = nt
        t0 = t * tile_bits
        x = int(dcw[k][p - t0])
        c = (x >> _CTRL_SHIFT) - 2
        if c < 0:
            raise chain_error(
                ERR_DC_TRUNCATED if c == -2 else ERR_DC_INVALID, b, p, nbits)
        q = p + (x & _ADV_MASK)
        o = int(outc[k][q - t0])
        kind = o & 3
        v = o >> 2
        if kind != _OK:     # AC invalid, truncated, overrun: in order
            raise chain_error(kind + 2, b, v, nbits)
        dc_starts.append(p)
        ac_starts.append(q)
        block_ids.append(b)
        p = v
    if block_ids:
        _emit_tile(win, t * tile_bits, dcw, acw, dc_starts, ac_starts,
                   block_ids, dc_out, ac_out, classes)
    return dc_out, ac_out


def unpack_bits_ref(payload: bytes, n_blocks: int, dc_table, ac_table, *,
                    tile_bits: int = TILE_BITS,
                    classes: tuple = rle.ONE_CLASS) -> tuple:
    """Staged NumPy decode of one entropy payload.

    Same contract as :func:`repro.core.entropy.rle.decode_payload`:
    returns ``(dc_diff (n_blocks,), ac (n_blocks, 63)) int32`` and
    raises the reference's errors on malformed streams.

    Args:
        payload: MSB-first packed entropy bytes (1-padded tail).
        n_blocks: number of 8x8 blocks encoded in the payload.
        dc_table: magnitude-category Huffman table (symbols <= 15).
        ac_table: (run, size) Huffman table.
        tile_bits: bit offsets staged per tile; any positive value
            decodes identically (tests shrink it to force blocks to
            straddle tile boundaries).
        classes: the table-class pattern; with more than one class,
            ``dc_table``/``ac_table`` hold one table per class.
    """
    dc_tables, ac_tables = rle.table_sets(dc_table, ac_table)
    rle.check_dc_tables(dc_tables)
    if n_blocks == 0:
        return np.zeros(0, np.int32), np.zeros((0, AC_LEN), np.int32)
    if tile_bits <= 0:
        raise ValueError(f"tile_bits must be positive, got {tile_bits}")
    nbits = len(payload) * 8
    win = bitio.bit_windows(payload)
    luts = [(huffman.decoder_luts(d), huffman.decoder_luts(a))
            for d, a in zip(dc_tables, ac_tables)]

    def get_tile(t):
        t0 = t * tile_bits
        w = min(tile_bits + MARGIN_BITS, nbits + 1 - t0)
        words = []
        for (dc_sym, dc_len), (ac_sym, ac_len) in luts:
            dcw = _unit_words(win, nbits, t0, w, dc_sym, dc_len)
            acw = _unit_words(win, nbits, t0, w, ac_sym, ac_len)
            words.append((dcw, acw, _ac_outcomes(acw, t0)))
        if classes == rle.ONE_CLASS:
            return words[0]
        return tuple(zip(*words))

    return resolve(win, nbits, n_blocks, tile_bits, get_tile, classes)
