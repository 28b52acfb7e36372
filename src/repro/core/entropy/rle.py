"""DC-differential + run-length symbolisation of zig-zag blocks.

Host-edge half of the entropy stage (NumPy): turns the fixed-shape
arrays produced by :mod:`repro.core.entropy.scan` into the JPEG-baseline
symbol stream that :mod:`huffman`/:mod:`bitio` serialise, and back.

The host symbolizer itself is :mod:`repro.core.entropy.dense` (one
fused whole-array pass); this module holds the alphabet, the table-class
helpers both directions share, and the decoders.
:func:`decode_payload` drives a precomputed peek-16-bit prefix-LUT
decoder whose per-bit-position symbol/advance/amplitude tables are
built in one vectorised pass, leaving only the (data-dependent) walk
along the symbol chain in Python.  The scalar implementations
:func:`symbolize_reference` / :func:`decode_payload_reference` (with
:func:`encode_payload` for the oracle's payload) are the golden oracles
the property tests and the ``entropy_throughput`` bench compare
against.  A third
decode family lives in ``repro.kernels.unpack_bits`` (speculative
per-offset decode + chain resolution, docs/decoding.md) and plugs in
through :func:`decode_payload`'s ``unpacker`` hook; all three agree on
values *and* errors by CI gate.

Symbol alphabet (docs/bitstream.md):

* DC: the magnitude category ``S`` of the DC difference (0..15), then
  ``S`` raw amplitude bits.
* AC: one byte ``(run << 4) | size`` per nonzero coefficient, where
  ``run`` is the number of zeros skipped (0..15) and ``size`` its
  magnitude category (1..15), then ``size`` amplitude bits.  Two
  specials: ``0x00`` (EOB) ends a block early, ``0xF0`` (ZRL) skips 16
  zeros without coding a coefficient.
* amplitudes use JPEG's one's-complement convention: ``v > 0`` codes as
  ``v``; ``v < 0`` codes as ``v + 2**size - 1``.

**Table classes.** A colour stream codes its blocks with more than one
pair of Huffman tables: block ``k`` uses the pair of class
``classes[k % len(classes)]`` (``(0, 0, 0, 0, 1, 1)`` for a 4:2:0 MCU,
luma then chroma; :data:`ONE_CLASS` for grayscale).  Wherever a
function takes ``classes``, its ``dc_table``/``ac_table`` may be one
table or a sequence indexed by class, and its histograms are per class.
"""

from __future__ import annotations

import numpy as np

from repro import obs
from repro.core.entropy import bitio, huffman

EOB = 0x00
ZRL = 0xF0
MAX_CATEGORY = 15          # amplitudes are at most 15 bits
AC_LEN = 63                # zig-zag positions 1..63


#: The class pattern of a grayscale stream: every block, class 0.
ONE_CLASS = (0,)


def table_sets(dc_table, ac_table) -> tuple:
    """``(dc_tables, ac_tables)``: a table, or a sequence of tables
    indexed by class, as tuples of one table per class."""
    def per_class(t):
        return tuple(t) if isinstance(t, (tuple, list)) else (t,)
    return per_class(dc_table), per_class(ac_table)


def block_classes(classes: tuple, n_blocks: int) -> np.ndarray:
    """(n_blocks,) int64 class of each block under a periodic pattern."""
    return np.resize(np.asarray(classes, np.int64), n_blocks)


def class_luts(tables: tuple) -> tuple:
    """(codes, lengths): (n_classes, 256) encoder LUTs, one row per class."""
    luts = [huffman.encoder_luts(t) for t in tables]
    return (np.stack([c for c, _ in luts]), np.stack([n for _, n in luts]))


class RangeError(ValueError):
    """A quantised level is too large for a 15-bit amplitude field."""


def magnitude_category(v: np.ndarray) -> np.ndarray:
    """Bit length of |v| per element (category 0 for v == 0)."""
    mag = np.abs(np.asarray(v, dtype=np.int64))
    # frexp exponent == bit length for exact integer floats; int64
    # magnitudes here are bounded well below 2**53 by the range check
    return np.where(mag == 0, 0,
                    np.frexp(mag.astype(np.float64))[1]).astype(np.int64)


def amplitude_value(v: np.ndarray, size: np.ndarray) -> np.ndarray:
    """One's-complement amplitude field for nonzero v of category size."""
    v = np.asarray(v, dtype=np.int64)
    return np.where(v >= 0, v, v + (1 << size) - 1)


def amplitude_decode(bits: int, size: int) -> int:
    """Invert :func:`amplitude_value` for one field."""
    if size == 0:
        return 0
    if bits < (1 << (size - 1)):
        return bits - (1 << size) + 1
    return bits


def _check_range(cat: np.ndarray, what: str) -> None:
    if cat.size and int(cat.max()) > MAX_CATEGORY:
        raise RangeError(
            f"{what} magnitude needs category {int(cat.max())} > "
            f"{MAX_CATEGORY}; levels must fit 15-bit amplitudes")


def symbolize_reference(dc_diff: np.ndarray, ac: np.ndarray) -> tuple:
    """Scalar per-block symbolisation: the encode oracle.

    The golden reference the property tests and the
    ``--check-identical`` bench gate compare the host symbolizer
    (:func:`repro.core.entropy.dense.symbolize_dense`) and the Pallas
    kernel against.  Not used on the production encode path.

    Args:
        dc_diff: (n,) int DC differences in block order.
        ac: (n, 63) int AC tails in zig-zag order.

    Returns:
        ``(is_dc, syms, amp_vals, amp_lens)`` — parallel arrays over the
        symbol stream in coding order (each block: one DC symbol, then
        its AC symbols).  ``amp_lens[k] == 0`` means symbol k carries no
        amplitude field (EOB/ZRL/zero DC diff).

    Raises:
        RangeError: some level needs an amplitude wider than 15 bits.
    """
    dc_diff = np.asarray(dc_diff, dtype=np.int64)
    ac = np.asarray(ac, dtype=np.int64)
    n = dc_diff.shape[0]
    dc_cat = magnitude_category(dc_diff)
    _check_range(dc_cat, "DC difference")
    ac_cat = magnitude_category(ac)
    _check_range(ac_cat, "AC coefficient")
    dc_amp = amplitude_value(dc_diff, dc_cat)
    ac_amp = amplitude_value(ac, ac_cat)

    is_dc, syms, amp_vals, amp_lens = [], [], [], []
    for b in range(n):
        is_dc.append(True)
        syms.append(int(dc_cat[b]))
        amp_vals.append(int(dc_amp[b]))
        amp_lens.append(int(dc_cat[b]))
        nz = np.nonzero(ac[b])[0]
        prev = -1
        for pos in nz:
            run = int(pos) - prev - 1
            while run >= 16:
                is_dc.append(False)
                syms.append(ZRL)
                amp_vals.append(0)
                amp_lens.append(0)
                run -= 16
            is_dc.append(False)
            syms.append((run << 4) | int(ac_cat[b, pos]))
            amp_vals.append(int(ac_amp[b, pos]))
            amp_lens.append(int(ac_cat[b, pos]))
            prev = int(pos)
        if prev != AC_LEN - 1:
            is_dc.append(False)
            syms.append(EOB)
            amp_vals.append(0)
            amp_lens.append(0)
    return (np.asarray(is_dc, dtype=bool),
            np.asarray(syms, dtype=np.int64),
            np.asarray(amp_vals, dtype=np.int64),
            np.asarray(amp_lens, dtype=np.int64))


def symbol_frequencies(is_dc, syms) -> tuple:
    """(dc_freqs, ac_freqs): 256-bin histograms of the two alphabets."""
    return (np.bincount(syms[is_dc], minlength=256),
            np.bincount(syms[~is_dc], minlength=256))


def codeword_fields(is_dc, syms, amp_vals, amp_lens, dc_table,
                    ac_table) -> tuple:
    """Codeword-lookup stage: symbol stream -> interleaved bit fields.

    Every symbol contributes its Huffman code, immediately followed by
    its amplitude field (when present); the interleave is realised by
    laying codes at even and amplitudes at odd slots of a (2M,) field
    array — packers drop the zero-width slots.

    Returns:
        ``(fields, widths)`` int64 arrays ready for any bit packer
        (:func:`repro.core.entropy.bitio.pack_bits` or the routed
        :mod:`repro.kernels.pack_bits` backend).

    Raises:
        ValueError: the stream contains a symbol the table cannot code
            (possible with shared tables; the container's cost-based
            selection never picks an uncovering table).
    """
    dc_code, dc_len = huffman.encoder_luts(dc_table)
    ac_code, ac_len = huffman.encoder_luts(ac_table)
    codes = np.where(is_dc, dc_code[syms], ac_code[syms])
    lens = np.where(is_dc, dc_len[syms], ac_len[syms])
    if bool((lens == 0).any()):
        raise ValueError("symbol stream contains a symbol absent from "
                         "the Huffman table")
    m = syms.shape[0]
    fields = np.empty(2 * m, dtype=np.int64)
    widths = np.empty(2 * m, dtype=np.int64)
    fields[0::2], widths[0::2] = codes, lens
    fields[1::2], widths[1::2] = amp_vals, amp_lens
    return fields, widths


def encode_payload(is_dc, syms, amp_vals, amp_lens, dc_table,
                   ac_table) -> bytes:
    """Huffman-code a symbol stream and pack it into bytes: the scalar
    oracle's payload stage (:func:`codeword_fields`, then
    :func:`repro.core.entropy.bitio.pack_bits`)."""
    return bitio.pack_bits(*codeword_fields(is_dc, syms, amp_vals,
                                            amp_lens, dc_table, ac_table))


_PAST_END = 32     # sentinel slots appended past the last window position

# packed per-position decode word: (ctrl + 2) << 23 | adv << 17 |
# (val + 32768); ctrl is the symbol byte, -1 = invalid prefix, -2 =
# a unit that needs bits past the payload end (truncation)
_CTRL_SHIFT = 23
_ADV_SHIFT = 17
_ADV_MASK = 0x3F
_VAL_MASK = 0x1FFFF
_VAL_BIAS = 32768
_SENTINEL = _VAL_BIAS      # ctrl -2, adv 0, val 0

# payloads up to this many bits get their packed tables converted to
# Python lists (~36 bytes per boxed entry, but the walk indexes them
# ~2.5x faster than ndarray scalars); larger payloads keep the int64
# ndarray so decode memory stays at 8 bytes per bit position per table
_WALK_LIST_MAX_BITS = 1 << 20

# payloads above this many bits are routed to the staged decoder
# (:func:`repro.kernels.unpack_bits.unpack_bits`, which selects its own
# backend) when :func:`decode_payload` is called without an ``unpacker``:
# the LUT walk's tables grow linearly with the payload
# (:func:`walk_table_nbytes` — ~16 B/bit across both alphabets on the
# ndarray branch) while the staged decoder's scratch is bounded per tile
# (:func:`repro.kernels.unpack_bits.ref.scratch_nbytes`), so a 100 MB
# payload costs ~13 GB of walk tables but < 3 MB of staged scratch
_ROUTED_DECODE_MIN_BITS = _WALK_LIST_MAX_BITS


def _decode_table(win: np.ndarray, nbits: int,
                  table: huffman.CanonicalTable):
    """Per-bit-position packed decode table for one Huffman alphabet.

    For every bit offset ``p`` of the payload (``win`` is its
    :func:`repro.core.entropy.bitio.bit_windows`, 1-padded past the end
    like the writer), assume a symbol of ``table`` starts at ``p`` and
    precompute — fully vectorised — one packed int per position holding:

    * ``ctrl`` — the decoded symbol byte, -1 for an invalid prefix, or
      -2 when the unit starting at ``p`` would need bits past the
      payload end (truncation, exactly when the reference reader's
      skip/take would run out),
    * ``adv``  — total bits the unit spans (code + amplitude field),
    * ``val``  — the amplitude field decoded to its signed value (for
      DC the field width is the symbol itself; for AC its low nibble —
      callers pick the table accordingly).

    Only the walk along the actual symbol chain (data-dependent) stays
    in Python; each step is one O(1) lookup plus shifts.  Returns a
    Python list for small payloads and the int64 ndarray above
    :data:`_WALK_LIST_MAX_BITS` (same indexing, bounded memory).
    """
    sym_lut, len_lut = huffman.decoder_luts(table)
    n = win.shape[0]
    # intermediates stay int32 (all values fit 17 bits) so the per-bit
    # precompute peaks at a few int32 arrays, not int64 ones; only the
    # final packed word widens to int64 (ctrl << 23 needs 32+ bits and
    # the walk's ndarray branch relies on signed arithmetic)
    sym = sym_lut[win].astype(np.int32)
    length = len_lut[win].astype(np.int32)
    # amplitude width: DC symbols *are* the width; AC keep the low nibble
    # (EOB=0x00 and ZRL=0xF0 both have a zero nibble => no field)
    size = np.where(sym > MAX_CATEGORY, sym & 0xF, sym)
    pidx = np.arange(n, dtype=np.int64)
    amp_at = np.minimum(pidx + length, n - 1)
    safe = np.maximum(size, 1)
    bits = win[amp_at].astype(np.int32) >> (bitio.MAX_FIELD_BITS - safe)
    val = np.where(bits < (1 << (safe - 1)), bits - (1 << safe) + 1, bits)
    val = np.where(size == 0, 0, val)
    ctrl = np.where(length == 0, 1, sym + 2)        # ctrl field, biased +2
    packed = ((ctrl.astype(np.int64) << _CTRL_SHIFT)
              | ((length + size).astype(np.int64) << _ADV_SHIFT)
              | (val + _VAL_BIAS))
    # a unit that would consume any bit past the payload end is
    # truncation, not decoding (mirrors the reference reader, which
    # raises before interpreting such bits); folding it into the packed
    # word keeps the walk at one branch per symbol, and the sentinel
    # tail covers any p a step can reach (a step advances < _PAST_END
    # bits) before the walk raises
    packed[pidx + length + size > nbits] = _SENTINEL
    packed = np.concatenate(
        [packed, np.full(_PAST_END, _SENTINEL, np.int64)])
    if nbits <= _WALK_LIST_MAX_BITS:
        return packed.tolist()
    return packed


def _staged_unpacker():
    """The routed staged decoder, or ``None`` without the kernels layer.

    Lazy so :mod:`repro.core.entropy` itself stays importable (and
    cheap) without jax — the import only runs for payloads above
    :data:`_ROUTED_DECODE_MIN_BITS`, and a missing/broken kernels layer
    falls back to the linear-memory ndarray walk rather than failing.
    """
    try:
        from repro.kernels import unpack_bits
    except Exception:       # pragma: no cover - kernels layer optional
        return None
    return unpack_bits.unpack_bits


def walk_table_nbytes(nbits: int) -> int:
    """Approximate resident bytes of both LUT-walk decode tables.

    :func:`_decode_table` materialises one packed word per payload bit
    position *per alphabet* — ~36 bytes per boxed entry on the
    list branch, 8 on the ndarray branch past
    :data:`_WALK_LIST_MAX_BITS` — so the walk's decode memory scales
    linearly with the payload.  The ``entropy_decode`` bench case
    reports this against the staged decoder's bounded per-tile scratch
    (:func:`repro.kernels.unpack_bits.ref.scratch_nbytes`).
    """
    entries = 2 * (nbits + 17 + _PAST_END)
    return entries * (36 if nbits <= _WALK_LIST_MAX_BITS else 8)


def check_dc_tables(dc_tables: tuple) -> None:
    """Reject a DC table coding a symbol above :data:`MAX_CATEGORY`."""
    for t in dc_tables:
        if t.symbols and max(t.symbols) > MAX_CATEGORY:
            raise ValueError(
                f"DC table codes symbol {max(t.symbols)} > "
                f"{MAX_CATEGORY}: not a magnitude-category alphabet")


def decode_payload(payload: bytes, n_blocks: int, dc_table, ac_table, *,
                   unpacker=None, classes: tuple = ONE_CLASS) -> tuple:
    """Decode ``n_blocks`` blocks from an entropy payload (LUT decoder).

    Replaces bit-at-a-time Huffman walking: the peek-16 prefix LUTs of
    both tables are applied to *every* bit position of the payload in
    one vectorised pass (:func:`_decode_table`), including amplitude
    extraction, so the remaining Python walk just follows the symbol
    chain with O(1) lookups per symbol.  Output is identical to
    :func:`decode_payload_reference` on every well-formed stream;
    malformed streams are always rejected by both, though the error
    *subtype* (truncation vs corruption) can differ in corner cases
    where padding bits mimic a valid symbol.

    Args:
        payload: packed bits from :func:`encode_payload`.
        n_blocks: how many 8x8 blocks the stream must contain (known
            from the container's image shape).
        dc_table: canonical table for DC categories; a table coding a
            symbol above :data:`MAX_CATEGORY` is rejected (the spec
            bounds DC categories to 0..15).
        ac_table: canonical table for AC (run, size) symbols.
        unpacker: optional ``(payload, n_blocks, dc_table, ac_table) ->
            (dc_diff, ac)`` callable replacing the whole decode, e.g.
            the routed :func:`repro.kernels.unpack_bits.unpack_bits`;
            ``None`` keeps the zero-indirection LUT walk below for
            payloads up to :data:`_ROUTED_DECODE_MIN_BITS` bits and
            routes larger ones to the staged decoder itself (the walk
            tables grow linearly with the payload; the staged scratch
            is bounded per tile).  Any
            unpacker must honour this function's full contract —
            values *and* errors (CI-gated by ``bench_entropy_throughput
            --check-identical``) — and takes ``classes=`` when the
            stream has more than one table class.
        classes: the table-class pattern (module docstring); with more
            than one class, ``dc_table``/``ac_table`` are sequences
            indexed by class.

    Returns:
        ``(dc_diff, ac)`` — (n,) int32 DC differences and (n, 63) int32
        AC tails, exactly inverting :func:`symbolize_reference`.

    Raises:
        bitio.TruncatedStream: the payload ends mid-block.
        ValueError: an invalid Huffman prefix, a coefficient overrun, or
            an out-of-spec DC table (corrupted stream).
    """
    extra = {} if classes == ONE_CLASS else {"classes": classes}
    if unpacker is not None:
        return unpacker(payload, n_blocks, dc_table, ac_table, **extra)
    dc_tables, ac_tables = table_sets(dc_table, ac_table)
    check_dc_tables(dc_tables)
    nbits = len(payload) * 8
    if nbits > _ROUTED_DECODE_MIN_BITS:
        # the walk tables below would cost ~16 B per payload bit; route
        # big payloads to the staged decoder's bounded per-tile scratch
        # (it picks its own backend via unpack_bits.select_backend)
        unpack = _staged_unpacker()
        if unpack is not None:
            return unpack(payload, n_blocks, dc_table, ac_table, **extra)
    with obs.route("unpack", "host", blocks=n_blocks,
                   table_classes=len(dc_tables)):
        return _walk(payload, nbits, n_blocks, dc_tables, ac_tables,
                     classes)


def _walk(payload: bytes, nbits: int, n_blocks: int, dc_tables: tuple,
          ac_tables: tuple, classes: tuple) -> tuple:
    """The LUT walk of :func:`decode_payload` (same contract)."""
    win = bitio.bit_windows(payload)
    dc_tabs = [_decode_table(win, nbits, t) for t in dc_tables]
    ac_tabs = [_decode_table(win, nbits, t) for t in ac_tables]
    period = len(classes)

    def bad(s: int, p: int, what: str):
        if s == -2:
            return bitio.TruncatedStream(
                f"entropy payload truncated: needed bit {p} of {nbits}")
        return ValueError(f"invalid {what} Huffman prefix at bit {p}")

    dc_out = [0] * n_blocks
    rows: list = []
    cols: list = []
    vals: list = []
    p = 0
    for b in range(n_blocks):
        c = classes[b % period]
        ac_tab = ac_tabs[c]
        x = dc_tabs[c][p]
        s = (x >> _CTRL_SHIFT) - 2
        if s < 0:
            raise bad(s, p, "DC")
        dc_out[b] = (x & _VAL_MASK) - _VAL_BIAS
        p += (x >> _ADV_SHIFT) & _ADV_MASK
        pos = 0                     # next AC slot to fill (0-based in ac)
        while pos < AC_LEN:
            x = ac_tab[p]
            s = (x >> _CTRL_SHIFT) - 2
            if s <= 0:
                if s < 0:
                    raise bad(s, p, "AC")
                p += (x >> _ADV_SHIFT) & _ADV_MASK   # EOB: rest is zero
                break
            if s == ZRL:
                pos += 16
                p += (x >> _ADV_SHIFT) & _ADV_MASK
                continue
            pos += s >> 4
            if pos >= AC_LEN:
                raise ValueError(
                    f"corrupted stream: AC run overruns block {b}")
            rows.append(b)
            cols.append(pos)
            vals.append((x & _VAL_MASK) - _VAL_BIAS)
            p += (x >> _ADV_SHIFT) & _ADV_MASK
            pos += 1
    if p > nbits:
        raise bitio.TruncatedStream(
            f"entropy payload truncated: needed bit {p} of {nbits}")
    ac = np.zeros((n_blocks, AC_LEN), dtype=np.int32)
    if rows:
        ac[rows, cols] = vals
    return np.asarray(dc_out, dtype=np.int32), ac


def decode_payload_reference(payload: bytes, n_blocks: int, dc_table,
                             ac_table, classes: tuple = ONE_CLASS
                             ) -> tuple:
    """Bit-at-a-time oracle for :func:`decode_payload` (same contract).

    The original :class:`repro.core.entropy.bitio.BitReader` walk, kept
    as the golden reference for the property tests and the
    ``--check-identical`` bench gate.  Not on the production path.
    """
    dc_tables, ac_tables = table_sets(dc_table, ac_table)
    dc_luts = [t.decoder_lut() for t in dc_tables]
    ac_luts = [t.decoder_lut() for t in ac_tables]
    reader = bitio.BitReader(payload)
    dc_diff = np.zeros(n_blocks, dtype=np.int32)
    ac = np.zeros((n_blocks, AC_LEN), dtype=np.int32)
    for b in range(n_blocks):
        c = classes[b % len(classes)]
        (dc_sym, dc_len), (ac_sym, ac_len) = dc_luts[c], ac_luts[c]
        w = reader.peek16()
        length = int(dc_len[w])
        if length == 0:
            raise ValueError(f"invalid DC Huffman prefix at bit "
                             f"{reader.pos}")
        reader.skip(length)
        size = int(dc_sym[w])
        dc_diff[b] = amplitude_decode(reader.take(size), size)
        pos = 0                     # next AC slot to fill (0-based in ac)
        while pos < AC_LEN:
            w = reader.peek16()
            length = int(ac_len[w])
            if length == 0:
                raise ValueError(f"invalid AC Huffman prefix at bit "
                                 f"{reader.pos}")
            reader.skip(length)
            sym = int(ac_sym[w])
            if sym == EOB:
                break
            if sym == ZRL:
                pos += 16
                continue
            run, size = sym >> 4, sym & 0xF
            pos += run
            if pos >= AC_LEN:
                raise ValueError(
                    f"corrupted stream: AC run overruns block {b}")
            ac[b, pos] = amplitude_decode(reader.take(size), size)
            pos += 1
    return dc_diff, ac
