"""Pallas TPU kernels and XLA walk: speculative Huffman decode.

Device-resident realisation of the decode half of the entropy stage,
mirroring :mod:`repro.kernels.pack_bits.kernel` on the encode side.
Huffman decode is serial in the *bit offset* chain, not in the work:
following Cloud et al. (arXiv:1107.1525), every program decodes **from
every candidate bit offset** at once, leaving only an O(1)-per-block
chain resolution.

The work splits in three device stages, the first two over the whole
payload:

* **unit words** (``unit_words_pallas``, Pallas) — every bit offset
  of the payload is an independent lookup, laid out lane-dense as
  ``(rows, 128)`` offsets and tiled over rows (and over table classes).
  Canonical bounds replace the 64K prefix LUT: the host hands in the
  tables' per-length ``(mincode, maxcode, valptr)`` triplets and symbol
  lists via scalar prefetch; a codeword is matched by 16 unrolled
  compares of the window's top ``L`` bits against the length-``L``
  bounds (prefix-free codes make at most one length match, so matches
  combine with ``where`` and no priority logic), and the symbol comes
  from a loop over the 256 symbol slots.
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
* **resolution** (``_resolve_kernel``, Pallas) — the scalar core hops
  the block chain through per-offset words held in SMEM, a window of
  the payload per sequential grid step, and writes each block's
  coefficients into (8, 128) VMEM tiles, one unit at a time: each
  offset's amplitude value was decoded beside its unit word
  (``_with_values``), so a unit is one SMEM read.  The first block
  whose chain stops early leaves an error record instead.

``unit_words_resolve`` chains the three into one program, so the host
uploads the windows once and fetches only the coefficients and the
error record; ``stage_tiles`` alone serves the host resolver
(:func:`repro.kernels.unpack_bits.ref.resolve`), which reads amplitudes
from the host's windows at resolved offsets.  Unit and outcome words
are bit-identical to :mod:`repro.kernels.unpack_bits.ref` at every
offset up to the payload's bit count, and the coefficients to
``ref.resolve``'s.
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
    c, i = pl.program_id(0), pl.program_id(1)
    base = c * N_PARAMS                 # this table class's parameters
    shape = win_ref.shape
    w16 = win_ref[...]
    pidx = ((i * shape[0] + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
            * LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    nbits = p_ref[base]
    dcw_ref[...] = _unit_words(w16, pidx, nbits, p_ref, base + _DC_BOUNDS,
                               base + _DC_SYMS)
    acw_ref[...] = _unit_words(w16, pidx, nbits, p_ref, base + _AC_BOUNDS,
                               base + _AC_SYMS)


@functools.partial(jax.jit, static_argnames=("interpret",))
def unit_words_pallas(params: jnp.ndarray, win: jnp.ndarray, *,
                      interpret: bool = True) -> tuple:
    """DC and AC unit words for every payload bit offset.

    Args:
        params: (N_PARAMS,) int32 scalar-prefetch — the payload bit
            count, then per-length canonical bounds ``mincode[16] |
            maxcode[16] | valptr[16]`` for the DC and the AC table
            (``maxcode == -1`` marks an unused code length), then each
            table's 256-slot symbol list in canonical order; or
            (C, N_PARAMS), one such row per table class.
        win: (rows, 128) int32 MSB-first 16-bit windows from
            ``bitio.bit_windows`` in offset order, padded with 0xFFFF;
            ``rows`` a multiple of :data:`ROWS`.
        interpret: run in Pallas interpret mode (non-TPU backends).

    Returns:
        ``(dc_words, ac_words)`` — (rows, 128) int32 unit words in the
        layout of :mod:`repro.kernels.unpack_bits.ref`, or (C, rows,
        128) with one plane per table class for 2-D ``params``.
    """
    rows = win.shape[0]
    if rows % ROWS or win.shape[1] != LANES:
        raise ValueError(f"windows {win.shape} are not ({ROWS}k, {LANES})")
    n_classes = 1 if params.ndim == 1 else params.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_classes, rows // ROWS),
        in_specs=[pl.BlockSpec((ROWS, LANES), lambda c, i, p: (i, 0))],
        out_specs=[pl.BlockSpec((None, ROWS, LANES),
                                lambda c, i, p: (c, i, 0))] * 2,
    )
    shape = jax.ShapeDtypeStruct((n_classes,) + win.shape, jnp.int32)
    dcw, acw = pl.pallas_call(
        _unit_kernel,
        out_shape=[shape, shape],
        grid_spec=grid_spec,
        interpret=interpret,
    )(params.reshape(-1), win)
    if params.ndim == 1:
        return dcw[0], acw[0]
    return dcw, acw


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


def _walk(acw: jnp.ndarray, period: int) -> jnp.ndarray:
    """Outcome words of the flat unit words ``acw``, which hold one or
    more planes of ``period`` offsets each (one per table class).

    Offsets past a plane's payload are terminal, so no chain crosses
    into the next plane and one walk serves every plane.
    """
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
    pos = jnp.arange(n, dtype=jnp.int32)
    return _outcome(y, pos if period == n else pos % period)


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
    return dc_words.reshape(-1), acw, _walk(acw, acw.shape[0])


def _with_values(words: jnp.ndarray, w16: jnp.ndarray) -> jnp.ndarray:
    """Unit words with their amplitude's value above them.

    ``(value << 15) | word`` at every offset: the ``size`` bits after
    the unit's code, read from the 16-bit window ``length <= 16``
    offsets ahead (a select over 16 static shifts), signed as in
    bitstream section 4; 0 for units without amplitude bits.
    """
    n = words.shape[0]
    ctrl = (words >> 6) - 2
    size = jnp.where(ctrl > 0, ctrl & 0xF, 0)
    length = (words & 0x3F) - size
    ahead = jnp.concatenate([w16, jnp.zeros(16, jnp.int32)])
    at = w16
    for k in range(1, 17):
        at = jnp.where(length == k, ahead[k:k + n], at)
    safe = jnp.maximum(size, 1)
    bits = at >> (16 - safe)
    val = jnp.where(bits < (1 << (safe - 1)), bits - (1 << safe) + 1, bits)
    return (jnp.where(size == 0, 0, val) << _VAL_SHIFT) | words


# Device resolution (``_resolve_kernel``): the scalar core follows the
# block chain through per-offset words held in SMEM, a window of offsets
# per grid step plus the next window, since a block reaches at most
# 2,000 bits past its start (``ref.MARGIN_BITS``).  Five windows per
# table class, double-buffered: RESOLVE_WORDS / classes offsets per
# window keeps them at 320 KiB of the v5e's 1 MiB of SMEM.
# Coefficients go out as (8, 128) int32 tiles of 16 blocks, 64 lanes per
# block: lane 0 the DC difference, lanes 1..63 the AC tail.
RESOLVE_WORDS = 8192
_GROUP_SHIFT = 4
GROUP = 1 << _GROUP_SHIFT             # blocks per output tile
_VAL_SHIFT = 15                       # value above the 15-bit unit word


def _resolve_kernel(meta_ref, *refs, classes: tuple, window: int):
    """One grid step: every block that starts in window ``i``.

    ``refs`` hold, per table class, the DC words of the window and the
    AC words and outcomes of the window and the next; then the
    resident output tiles, the error record ``(kind, block, bit)`` and
    the chain state ``(p, b, b mod len(classes))`` carried across steps.
    """
    n_cls = max(classes) + 1
    ins, (out_ref, err_ref, st_ref) = refs[:5 * n_cls], refs[5 * n_cls:]
    du = ins[0::5]
    au = list(zip(ins[1::5], ins[2::5]))
    oc = list(zip(ins[3::5], ins[4::5]))
    i = pl.program_id(0)
    base = i * window

    @pl.when(i == 0)
    def _():
        for j in range(3):
            st_ref[j] = 0
            err_ref[j] = 0

    def by_class(k, read):
        v = read(0)
        for j in range(1, n_cls):
            v = jnp.where(k == j, read(j), v)
        return v

    def near(pairs, k, off):
        """The word at offset ``off`` of this window or the next."""
        r = off - base
        lo, hi = jnp.minimum(r, window - 1), jnp.maximum(r - window, 0)
        return by_class(k, lambda j: jnp.where(r < window, pairs[j][0][lo],
                                               pairs[j][1][hi]))

    idx = (jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1))

    def emit(b, x, q, k):
        g = b >> _GROUP_SHIFT
        slot = ((b >> 1) & 7) * LANES + (b & 1) * 64
        tile = jnp.where((b & (GROUP - 1)) == 0,
                         jnp.zeros((8, LANES), jnp.int32), out_ref[g])
        tile = jnp.where(idx == slot, x >> _VAL_SHIFT, tile)

        def unit(c):
            q, pos, tile, _ = c
            u = near(au, k, q)
            ctrl = ((u >> 6) & 0x1FF) - 2
            run = ctrl >> 4
            # EOB and ZRL carry value 0, written at or past their own
            # position: into lanes that no unit has written yet
            tile = jnp.where(idx == slot + 1 + pos + run, u >> _VAL_SHIFT,
                             tile)
            pos = pos + jnp.where(ctrl == 0, 0, run + 1)
            return q + (u & 0x3F), pos, tile, (ctrl == 0) | (pos >= 63)

        _, _, tile, _ = jax.lax.while_loop(
            lambda c: jnp.logical_not(c[3]), unit,
            (q, jnp.int32(0), tile, False))
        out_ref[g] = tile

    def block(c):
        p, b, m, _ = c
        k = jnp.int32(0)
        for j, cls in enumerate(classes):
            if cls:
                k = jnp.where(m == j, cls, k)
        x = by_class(k, lambda j: du[j][p - base])
        ctrl = ((x >> 6) & 0x1FF) - 2
        q = p + (x & 0x3F)
        o = near(oc, k, q)
        kind = jnp.where(ctrl == -2, _ref.ERR_DC_TRUNCATED,
                         jnp.where(ctrl == -1, _ref.ERR_DC_INVALID,
                                   jnp.where((o & 3) == 0, 0, (o & 3) + 2)))
        ok = kind == 0

        @pl.when(ok)
        def _():
            emit(b, x, q, k)

        @pl.when(jnp.logical_not(ok))
        def _():
            err_ref[0] = kind
            err_ref[1] = b
            err_ref[2] = jnp.where(ctrl < 0, p, o >> 2)

        m = jnp.where(m + 1 == len(classes), 0, m + 1)
        return (jnp.where(ok, o >> 2, p), b + 1, m, jnp.logical_not(ok))

    n_blocks = meta_ref[0]
    p, b, m, _ = jax.lax.while_loop(
        lambda c: (jnp.logical_not(c[3]) & (c[1] < n_blocks)
                   & (c[0] < base + window)),
        block, (st_ref[0], st_ref[1], st_ref[2], err_ref[0] != 0))
    st_ref[0] = p
    st_ref[1] = b
    st_ref[2] = m


def _resolve(n_blocks: jnp.ndarray, du: jnp.ndarray, au: jnp.ndarray,
             oc: jnp.ndarray, *, n: int, block_rows: int, classes: tuple,
             interpret: bool) -> tuple:
    """Launch ``_resolve_kernel`` over flat per-class planes of ``n``
    offsets; returns the (block_rows, 8, 128) tiles and the error
    record."""
    window = min(RESOLVE_WORDS // (max(classes) + 1), n)
    nw = n // window
    smem = pltpu.SMEM

    def at(k, ahead):
        if ahead:
            return pl.BlockSpec((window,), lambda i, m: (
                k * nw + jnp.minimum(i + 1, nw - 1),), memory_space=smem)
        return pl.BlockSpec((window,), lambda i, m: (k * nw + i,),
                            memory_space=smem)

    in_specs, args = [], []
    for k in range(max(classes) + 1):
        in_specs += [at(k, False), at(k, False), at(k, True), at(k, False),
                     at(k, True)]
        args += [du, au, au, oc, oc]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nw,),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((block_rows, 8, LANES),
                                lambda i, m: (0, 0, 0)),
                   pl.BlockSpec((3,), lambda i, m: (0,), memory_space=smem)],
        scratch_shapes=[pltpu.SMEM((3,), jnp.int32)],
    )
    return pl.pallas_call(
        functools.partial(_resolve_kernel, classes=classes, window=window),
        out_shape=[jax.ShapeDtypeStruct((block_rows, 8, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((3,), jnp.int32)],
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(n_blocks.reshape(1), *args)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "classes", "interpret"))
def unit_words_resolve(params: jnp.ndarray, win: jnp.ndarray, *,
                       block_rows: int, classes: tuple,
                       interpret: bool = True) -> tuple:
    """The whole device decode of one payload, as one program.

    Unit words of every class (``unit_words_pallas``), the chain walk of
    every offset (as ``stage_tiles``), each unit's amplitude value, and
    the scalar chain resolution and coefficient emission
    (``_resolve_kernel``).

    Args:
        params: int32 — the block count, then one ``N_PARAMS`` row per
            table class (as ``unit_words_pallas`` takes them).
        win: (rows, 128) int32 16-bit windows, as ``unit_words_pallas``.
        block_rows: output tiles; ``16 * block_rows >= n_blocks``.
        classes: the table-class pattern.

    Returns:
        ``(coefs, err)`` — (16 * block_rows, 64) int16, one row per
        block (DC difference, then the AC tail; rows past the last
        block decoded are unspecified), and the (3,) int32 error record
        ``(kind, block, bit)``, kind 0 when every block decoded.
    """
    n_cls = max(classes) + 1
    dcw, acw = unit_words_pallas(params[1:].reshape(n_cls, N_PARAMS), win,
                                 interpret=interpret)
    n = win.size
    w16 = jnp.tile(win.reshape(-1), n_cls)
    acw = acw.reshape(-1)
    coefs, err = _resolve(params[0], _with_values(dcw.reshape(-1), w16),
                          _with_values(acw, w16), _walk(acw, n), n=n,
                          block_rows=block_rows, classes=classes,
                          interpret=interpret)
    return coefs.astype(jnp.int16).reshape(-1, 64), err
