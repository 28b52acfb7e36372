"""The host symbolizer: one fused NumPy pass over a dense block layout.

Turns zig-zag blocks into the JPEG symbol stream, its amplitude fields
and the per-alphabet histograms, as whole-array NumPy over a **dense
per-block layout**: every 8x8 block owns 64 symbol slots (slot 0 is its
DC symbol; a block never emits more than 64 symbols — 1 DC + at most 63
coefficient units + EOB, and the three possible ZRL expansions only
occur when coefficient units are scarce), so symbolisation becomes pure
fixed-shape array arithmetic with no data-dependent output size:

1. **runs** — each nonzero AC coefficient's zero run is its zig-zag
   position minus the previous nonzero position, giving its ZRL
   expansion count and (run, size) symbol;
2. **slots** — an exclusive prefix sum of per-coefficient unit counts
   places every ZRL and coded symbol at a dense slot; EOB slots stay at
   the zero-initialised ``(EOB, no amplitude)``;
3. **histograms** — the per-alphabet 256-bin histograms fall out of the
   same pass (DC categories + coded symbols + ZRL/EOB counts), without
   materialising the compacted stream;
4. **compaction** — a validity mask (slot index < per-block total)
   flattens the dense arrays into the coding-order stream, element-
   identical to :func:`repro.core.entropy.rle.symbolize_reference`.

The Pallas symbolize kernel (:mod:`repro.kernels.symbolize`) runs the
same stages as fixed-shape lane arithmetic on the device, against this
pass as its reference.  :func:`prepare` is the container encoders'
default ``symbolizer=``: on every platform, streams the device route
does not take are symbolized here.  The module imports without jax.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.core.entropy import bitio, huffman, rle

AC_LEN = rle.AC_LEN            # 63 zig-zag AC positions
SLOTS = 64                     # dense symbol slots per block (see above)
# a coefficient at zig-zag position p <= 62 can skip at most 62 zeros,
# so it emits at most floor(62/16) = 3 ZRL expansions
MAX_ZRL = (AC_LEN - 1) // 16


@dataclasses.dataclass(frozen=True)
class DenseSymbols:
    """One fused symbolisation pass over a batch of blocks.

    ``syms``/``amp_vals``/``amp_lens`` are (n, 64) dense per-block
    slot arrays (slot 0 = DC; slots past ``total[b]`` are meaningless);
    ``total`` is the per-block symbol count; ``dc_freq``/``ac_freq``
    are the 256-bin alphabet histograms
    (:func:`repro.core.entropy.rle.symbol_frequencies` of the stream).

    The slot arrays are **int16**: symbols and amplitude widths are
    bytes, amplitude values fit 15 bits (the oracle's RangeError
    guard), and the dense layout's cost is dominated by touching
    3 x (n, 64) fresh pages per call, so narrow lanes keep it cheap.
    :func:`dense_to_stream` widens gathers back to the int64 stream
    contract.
    """
    syms: np.ndarray           # (n, 64) int16
    amp_vals: np.ndarray       # (n, 64) int16
    amp_lens: np.ndarray       # (n, 64) int16
    total: np.ndarray          # (n,) int64, in [1, 64]
    dc_freq: np.ndarray        # (256,) int64; (n_classes, 256) with classes
    ac_freq: np.ndarray        # (256,) int64; (n_classes, 256) with classes
    classes: tuple = rle.ONE_CLASS   # the blocks' table-class pattern


def _class_hist(sym: np.ndarray, cls: np.ndarray, n_classes: int,
                weights=None) -> np.ndarray:
    """(n_classes, 256) histogram of ``sym`` split by ``cls``."""
    return np.bincount(cls * 256 + sym, weights=weights,
                       minlength=n_classes * 256).astype(np.int64).reshape(
                           n_classes, 256)


def symbolize_dense(dc_diff: np.ndarray, ac: np.ndarray,
                    classes: tuple = rle.ONE_CLASS) -> DenseSymbols:
    """Blocks -> dense per-block symbol slots + histograms, one pass.

    Args:
        dc_diff: (n,) int DC differences in block order.
        ac: (n, 63) int AC tails in zig-zag order.
        classes: the table-class pattern (:mod:`repro.core.entropy.rle`);
            with more than one class the histograms are per class.

    Raises:
        rle.RangeError: some level needs an amplitude wider than 15
            bits (same message as the scalar oracle, DC checked first).
    """
    dc_diff = np.asarray(dc_diff, dtype=np.int64)
    ac = np.asarray(ac, dtype=np.int64)
    n = dc_diff.shape[0]
    if ac.shape != (n, AC_LEN):
        raise ValueError(f"ac shape {ac.shape} does not match "
                         f"({n}, {AC_LEN})")
    dc_cat = rle.magnitude_category(dc_diff)
    rle._check_range(dc_cat, "DC difference")
    dc_amp = rle.amplitude_value(dc_diff, dc_cat)
    # per-element work happens on the gathered nonzeros (O(nnz), the
    # host-side analogue of the kernel's all-lanes arithmetic; quantised
    # AC tails are sparse); np.nonzero is row-major, which IS coding
    # order within each block
    flat = np.flatnonzero(ac.reshape(-1) != 0)
    rows, cols = divmod(flat, AC_LEN)
    vals = ac.reshape(-1)[flat]
    cat = rle.magnitude_category(vals)
    rle._check_range(cat, "AC coefficient")
    amp = rle.amplitude_value(vals, cat)

    # previous nonzero position within the row: the predecessor element,
    # or -1 at each row's first nonzero
    first = np.empty(rows.shape, bool)
    first[:1] = True
    first[1:] = rows[1:] != rows[:-1]
    prev = np.empty_like(cols)
    prev[1:] = cols[:-1]
    prev[first] = -1
    run = cols - prev - 1
    zrl = run >> 4                       # ZRL expansions before the symbol
    unit = zrl + 1                       # symbols this coefficient emits
    # within-row exclusive prefix sum of units = global running sum
    # minus the base at the row's first nonzero
    excl = np.cumsum(unit) - unit
    seg = np.cumsum(first) - 1           # nonzero -> its row-segment id
    start = 1 + excl - excl[first][seg]  # slot of the unit's first symbol
    base = rows * SLOTS                  # flat scatter addresses, once
    idx = base + start + zrl             # each coefficient's coded slot

    unit_b = np.zeros(n, np.int64)
    np.add.at(unit_b, rows, unit)
    last = np.full(n, -1, np.int64)
    last[rows] = cols                    # row-major: the max col wins
    eob = last != AC_LEN - 1
    total = 1 + unit_b + eob

    # dense scatter; EOB slots keep the zero init.  One int16 buffer:
    # the pass's cost is dominated by faulting the fresh dense pages,
    # so three narrow planes behind one allocation beat three int64
    # arrays ~4x on memory touched
    buf = np.zeros((3, n, SLOTS), np.int16)
    syms_d, amps_d, lens_d = buf
    flat_syms = syms_d.reshape(-1)
    syms_d[:, 0] = dc_cat
    amps_d[:, 0] = dc_amp
    lens_d[:, 0] = dc_cat
    coef_sym = ((run & 15) << 4) | cat
    flat_syms[idx] = coef_sym
    amps_d.reshape(-1)[idx] = amp
    lens_d.reshape(-1)[idx] = cat
    zidx = base + start
    for t in range(MAX_ZRL):
        live = zrl > t
        flat_syms[zidx[live] + t] = rle.ZRL

    # coded symbols never collide with ZRL (their size nibble is >= 1)
    # or EOB (nonzero), so the three contributions just add
    if classes == rle.ONE_CLASS:
        dc_freq = np.bincount(dc_cat, minlength=256)
        ac_freq = np.bincount(coef_sym, minlength=256)
        ac_freq[rle.ZRL] += int(zrl.sum())
        ac_freq[rle.EOB] += int(eob.sum())
    else:
        n_cls = max(classes) + 1
        cls = rle.block_classes(classes, n)
        dc_freq = _class_hist(dc_cat, cls, n_cls)
        ac_freq = _class_hist(coef_sym, cls[rows], n_cls)
        ac_freq[:, rle.ZRL] += np.bincount(cls[rows], weights=zrl,
                                           minlength=n_cls).astype(np.int64)
        ac_freq[:, rle.EOB] += np.bincount(cls[eob],
                                           minlength=n_cls).astype(np.int64)
    return DenseSymbols(syms=syms_d, amp_vals=amps_d, amp_lens=lens_d,
                        total=total, dc_freq=dc_freq, ac_freq=ac_freq,
                        classes=classes)


def dense_to_stream(dense: DenseSymbols) -> tuple:
    """Compact dense slots into the coding-order symbol stream.

    Returns ``(is_dc, syms, amp_vals, amp_lens)`` with the exact
    contract (dtypes included) of
    :func:`repro.core.entropy.rle.symbolize_reference`.
    """
    slot = np.arange(SLOTS)
    valid = slot < dense.total[:, None]
    is_dc = np.broadcast_to(slot == 0, valid.shape)[valid]
    return (is_dc,
            dense.syms[valid].astype(np.int64),
            dense.amp_vals[valid].astype(np.int64),
            dense.amp_lens[valid].astype(np.int64))


def encode_fields_dense(dense: DenseSymbols, dc_table, ac_table) -> tuple:
    """Codeword lookup on the dense layout: -> (fields, widths).

    Valid slots are addressed by flat index (per-block prefix sums of
    ``total``), so the lookup touches O(stream) elements; each
    contributes its Huffman code then its amplitude field, and the
    row-major interleave *is* the stream order.  Byte output equals
    :func:`repro.core.entropy.rle.codeword_fields` + the same packer
    (zero-width amplitude fields are dropped by every packer).  With
    more than one table class, ``dc_table``/``ac_table`` hold one table
    per class and each block takes its class's codes.

    Raises:
        ValueError: a valid slot holds a symbol its table cannot code
            (same message as ``codeword_fields``).
    """
    n = dense.syms.shape[0]
    # flat indices of the valid slots, in coding order: slot arithmetic
    # on O(stream) elements, not O(n * 64) lanes
    k = int(dense.total.sum())
    row = np.repeat(np.arange(n, dtype=np.int64), dense.total)
    cum = np.cumsum(dense.total)
    slot = np.arange(k, dtype=np.int64) - np.repeat(cum - dense.total,
                                                    dense.total)
    syms = dense.syms.reshape(-1)[row * SLOTS + slot]
    is_dc = slot == 0
    if dense.classes == rle.ONE_CLASS:
        dc_code, dc_len = huffman.encoder_luts(dc_table)
        ac_code, ac_len = huffman.encoder_luts(ac_table)
        codes = np.where(is_dc, dc_code[syms], ac_code[syms])
        lens = np.where(is_dc, dc_len[syms], ac_len[syms])
    else:
        dc_code, dc_len = rle.class_luts(dc_table)
        ac_code, ac_len = rle.class_luts(ac_table)
        cls = rle.block_classes(dense.classes, n)[row]
        codes = np.where(is_dc, dc_code[cls, syms], ac_code[cls, syms])
        lens = np.where(is_dc, dc_len[cls, syms], ac_len[cls, syms])
    if bool((lens == 0).any()):
        raise ValueError("symbol stream contains a symbol absent from "
                         "the Huffman table")
    fields = np.empty((k, 2), np.int64)
    widths = np.empty((k, 2), np.int64)
    fields[:, 0] = codes
    fields[:, 1] = dense.amp_vals.reshape(-1)[row * SLOTS + slot]
    widths[:, 0] = lens
    widths[:, 1] = dense.amp_lens.reshape(-1)[row * SLOTS + slot]
    return fields.reshape(-1), widths.reshape(-1)


def encode_payload_dense(dense: DenseSymbols, dc_table, ac_table,
                         packer=None) -> bytes:
    """Dense codeword lookup + bit packing; byte-identical to
    :func:`repro.core.entropy.rle.encode_payload` on the same stream.
    ``packer`` is a ``(fields, widths) -> bytes`` callable; ``None``
    packs with :func:`repro.core.entropy.bitio.pack_bits`."""
    fields, widths = encode_fields_dense(dense, dc_table, ac_table)
    if packer is not None:
        return packer(fields, widths)
    with obs.route("pack", "host"):
        return bitio.pack_bits(fields, widths)


class _NumpyPrepared:
    """Fused host preparation: dense pass now, one packer call later."""

    def __init__(self, dense: DenseSymbols, packer):
        self._dense = dense
        self._packer = packer
        self.dc_freq = dense.dc_freq
        self.ac_freq = dense.ac_freq

    def payload(self, dc_table, ac_table) -> bytes:
        """Huffman-code + pack the prepared stream for chosen tables
        (one per class where the stream has several)."""
        return encode_payload_dense(self._dense, dc_table, ac_table,
                                    packer=self._packer)


def prepare(dc_diff, ac, packer=None, classes: tuple = rle.ONE_CLASS):
    """The container encoders' default ``symbolizer=``: the host route.

    Returns a prepared stream with ``dc_freq``/``ac_freq`` histograms —
    all table negotiation needs — and ``payload(dc_table, ac_table) ->
    bytes``, which codes and packs with ``packer`` (``None`` = the NumPy
    packer).  With more than one table class the histograms are
    (n_classes, 256) and ``payload`` takes one table per class.

    Raises:
        rle.RangeError: some level needs an amplitude wider than 15
            bits (the scalar oracle's message).
    """
    with obs.route("symbolize", "host", blocks=len(dc_diff)):
        return _NumpyPrepared(symbolize_dense(dc_diff, ac, classes), packer)
