"""Routed public wrappers for the unpack_bits kernel.

``unpack_bits`` is the decode backend the entropy layer routes through
via ``rle.decode_payload(unpacker=)``: on TPU one device program decodes
the whole payload — unit words, chain walk, block-chain resolution and
coefficient emission — and only ``(dc_diff, ac)`` comes back; the staged
NumPy reference (``backend="numpy"``) decodes the streams outside the
device route's guards.  :func:`make_unpacker` is where the engine's
decode picks the route: this device decode on a TPU, the LUT walk of
:func:`repro.core.entropy.rle.decode_payload` elsewhere.
Coefficient-identical output on every route (CI-gated by
``bench_entropy_throughput --check-identical``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.entropy import bitio, huffman, rle
from repro.kernels.unpack_bits import kernel, ref

# Above this many payload bits the stream decodes with the NumPy
# reference.  VMEM does not bound it: the unit-word kernel streams
# (16, 128) int32 blocks of bit windows (8 KiB in, 16 KiB out per
# program) whatever the payload size, the chain walk is element-wise
# XLA and the resolver holds two windows of words per array in SMEM.
# What grows is HBM: about 28 B per staged offset and class (windows,
# two unit-word planes, outcomes, two value planes), offsets bucketed
# to a power of two.  Compiled for a TPU v5e at 2**20 bits (a ~128 KB
# payload; 2**21 offsets), ``memory_analysis()`` of the whole-stream
# program ``kernel.unit_words_resolve`` gives 8,392,704 B in and 0 B of
# HBM temporaries for one table class, 8,396,800 B in and 16,906,240 B
# of temporaries for two (tests/test_tpu_compile.py).
MAX_DEVICE_BITS = 1 << 20

# Above this many blocks the device stages the payload and the host
# resolves the chain (``ref.resolve``).  The resolver keeps every output
# tile resident in VMEM, 256 B per block: 4 MiB at 2**14 blocks (a
# 1024x1024 grayscale image; a Kodak colour photo holds 9,216), which
# double-buffered stays inside the 16 MiB of scoped VMEM that Mosaic
# grants a kernel on a v5e.  Compiled there at MAX_DEVICE_BITS,
# ``memory_analysis()`` gives 2,098,176 B of output (the int16
# coefficients and the error record) at 2**14 blocks and one class,
# 1,180,672 B at 9,216 blocks and two.
MAX_DEVICE_BLOCKS = 1 << 14

# Table classes the device resolver takes: its SMEM windows shrink with
# the classes (``kernel.RESOLVE_WORDS``) and must hold a block's reach.
MAX_DEVICE_CLASSES = 2

# Blocks per bucket of the resolver's output, so that a workload sees a
# bounded set of compiled shapes.
BLOCK_BUCKET = 1024

BACKENDS = ("pallas", "numpy")

scratch_nbytes = ref.scratch_nbytes


def select_backend(backend: str = "auto") -> str:
    """Resolve the unpacking backend name ("pallas" on TPU, else "numpy")."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend not in BACKENDS:
        raise ValueError(f"unknown unpack_bits backend {backend!r}; "
                         f"expected one of {('auto',) + BACKENDS}")
    return backend


def unpack_bits(payload: bytes, n_blocks: int, dc_table, ac_table, *,
                backend: str = "auto",
                tile_bits: int | None = None,
                interpret: bool | None = None,
                classes: tuple = rle.ONE_CLASS) -> tuple:
    """Decode one entropy payload into ``(dc_diff, ac)`` coefficients.

    Same contract as :func:`repro.core.entropy.rle.decode_payload`
    (same values, same errors at the same bit offsets), with the
    speculative stage routed per backend.

    Args:
        payload: MSB-first packed entropy bytes (1-padded tail).
        n_blocks: number of 8x8 blocks encoded in the payload.
        dc_table: magnitude-category Huffman table (symbols <= 15).
        ac_table: (run, size) Huffman table.
        backend: "auto" (Pallas on TPU, NumPy elsewhere), "pallas", or
            "numpy".
        tile_bits: bit offsets per host resolver tile; ``None``
            resolves the chain and emits the values on the device (a
            stream over ``MAX_DEVICE_BLOCKS`` or ``MAX_DEVICE_CLASSES``
            resolves on the host, as one tile).  Ignored by "numpy".
            The device stage covers the whole payload either way.
        interpret: Pallas interpret-mode override (None = interpret
            exactly when no TPU is present); ignored by "numpy".
        classes: the table-class pattern (:mod:`repro.core.entropy.rle`);
            with more than one class, ``dc_table``/``ac_table`` hold one
            table per class and the device stages each class's words.

    Returns:
        ``(dc_diff (n_blocks,) int32, ac (n_blocks, 63) int32)``,
        identical across backends and across every ``tile_bits``.
    """
    if select_backend(backend) == "numpy":
        with _host_route(n_blocks, classes):
            return ref.unpack_bits_ref(payload, n_blocks, dc_table,
                                       ac_table, classes=classes)
    return _unpack_device(payload, n_blocks, dc_table, ac_table, interpret,
                          tile_bits, classes)


def _host_route(n_blocks: int, classes: tuple):
    """The unpack span of a stream decoded by the NumPy reference,
    which resolves its chain on the host."""
    obs.count("entropy.resolve.host")
    return obs.route("unpack", "host", blocks=n_blocks,
                     table_classes=max(classes) + 1)


def make_unpacker():
    """The decode's unpacking route, chosen from the platform.

    ``None`` off the TPU — callers then keep their zero-indirection
    default (the LUT walk inside
    :func:`repro.core.entropy.rle.decode_payload`) — and the routed
    device decode on a TPU, which resolves each stream's block chain on
    the device within its guards.
    """
    if select_backend() == "numpy":
        return None
    return functools.partial(unpack_bits, backend="pallas")


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def table_params(table: huffman.CanonicalTable) -> tuple:
    """Canonical decode parameters for the kernel's bounds matcher.

    Returns ``(params (48,) int32, symbols (256,) int32)`` where
    ``params`` is ``mincode[16] | maxcode[16] | valptr[16]``:
    at code length ``L`` (1-based), valid codes are exactly
    ``mincode[L-1] .. maxcode[L-1]`` (``maxcode == -1`` when the table
    has no codes of that length) and the matching symbol is
    ``symbols[valptr[L-1] + code - mincode[L-1]]`` — the classic
    T.81 F.2.2.3 decoder state, here evaluated for all 16 lengths at
    once since prefix-free codes make at most one length match.
    """
    params = np.full(48, -1, np.int32)
    syms = np.zeros(256, np.int32)
    syms[:len(table.symbols)] = table.symbols
    code = 0
    k = 0
    for i, c in enumerate(table.counts):
        if c:
            params[i] = code                # mincode
            params[16 + i] = code + c - 1   # maxcode
            params[32 + i] = k              # valptr
        else:
            params[i] = 0
            params[32 + i] = 0
        code = (code + c) << 1
        k += c
    return params, syms


def _unpack_device(payload: bytes, n_blocks: int, dc_table, ac_table,
                   interpret: bool | None, tile_bits: int | None = None,
                   classes: tuple = rle.ONE_CLASS) -> tuple:
    """Host orchestration of the device speculative decode.

    With ``tile_bits=None`` (the engine's route) one program decodes
    the payload, chain and values included
    (:func:`resolve_on_device`).  An explicit ``tile_bits``, or a stream
    over the resolver's guards, stages on the device and resolves on
    the host (:func:`_unpack_staged`).  Offsets are bucketed to powers
    of two so a streaming workload sees a bounded set of compiled
    shapes.
    """
    from repro.kernels import common
    if interpret is None:
        interpret = common.interpret_default()
    rle.check_dc_tables(rle.table_sets(dc_table, ac_table)[0])
    if n_blocks == 0:
        return (np.zeros(0, np.int32), np.zeros((0, ref.AC_LEN), np.int32))
    nbits = len(payload) * 8
    if nbits == 0 or nbits > MAX_DEVICE_BITS:
        with _host_route(n_blocks, classes):
            return ref.unpack_bits_ref(payload, n_blocks, dc_table,
                                       ac_table, classes=classes)
    with obs.device_route("unpack", interpret, blocks=n_blocks,
                          table_classes=max(classes) + 1):
        if (tile_bits is None and n_blocks <= MAX_DEVICE_BLOCKS
                and max(classes) < MAX_DEVICE_CLASSES):
            return resolve_on_device(payload, nbits, n_blocks, dc_table,
                                     ac_table, interpret, classes)
        return _unpack_staged(payload, nbits, n_blocks, dc_table, ac_table,
                              interpret, tile_bits, classes)


def _windows(payload: bytes, nbits: int) -> tuple:
    """``(win, win_pad)``: the payload's 16-bit windows, and a copy
    padded with 0xFFFF to ``max(pow2(nbits + 1 + MAX_ADV), 2048)``
    offsets."""
    win = bitio.bit_windows(payload)
    n_pad = max(_pow2(nbits + 1 + kernel.MAX_ADV), kernel.ROWS * kernel.LANES)
    win_pad = np.full(n_pad, 0xFFFF, np.int32)
    win_pad[:win.size] = win
    return win, win_pad


def _params(nbits: int, dc_table: huffman.CanonicalTable,
            ac_table: huffman.CanonicalTable) -> np.ndarray:
    """One class's ``kernel.N_PARAMS`` scalar-prefetch row."""
    dc_params, dc_syms = table_params(dc_table)
    ac_params, ac_syms = table_params(ac_table)
    return np.concatenate([np.array([nbits], np.int32), dc_params,
                           ac_params, dc_syms, ac_syms])


def resolve_on_device(payload: bytes, nbits: int, n_blocks: int, dc_table,
                      ac_table, interpret: bool,
                      classes: tuple = rle.ONE_CLASS) -> tuple:
    """Upload, decode and fetch one payload with ``unit_words_resolve``.

    Only the int16 coefficients of the block bucket and the 3-word error
    record cross back; a chain that stops early raises what
    ``rle.decode_payload`` raises, from the record.
    """
    _, win_pad = _windows(payload, nbits)
    params = np.concatenate(
        [np.array([n_blocks], np.int32)]
        + [_params(nbits, d, a)
           for d, a in zip(*rle.table_sets(dc_table, ac_table))])
    with obs.h2d(params, win_pad):
        params_d = jnp.asarray(params)
        win_d = jnp.asarray(win_pad.reshape(-1, kernel.LANES))
    bucket = -(-n_blocks // BLOCK_BUCKET) * BLOCK_BUCKET
    coefs, err = kernel.unit_words_resolve(
        params_d, win_d, block_rows=bucket // kernel.GROUP, classes=classes,
        interpret=interpret)
    obs.launched("unpack", coefs)
    obs.count("entropy.resolve.device")
    with obs.d2h(coefs, err):
        coefs, err = jax.device_get((coefs, err))
    kind, block, bit = (int(v) for v in err)
    if kind:
        raise ref.chain_error(kind, block, bit, nbits)
    z = coefs[:n_blocks].astype(np.int32)
    return z[:, 0], z[:, 1:]


def stage(payload: bytes, nbits: int, dc_table: huffman.CanonicalTable,
          ac_table: huffman.CanonicalTable, interpret: bool) -> tuple:
    """Upload, launch and fetch the device stage of one payload.

    Returns ``(win, dc_words, ac_words, outcomes)``: the host's 16-bit
    windows and flat int32 arrays covering offsets ``0 .. n - 1``,
    ``n = max(pow2(nbits + 1 + MAX_ADV), 2048)``.
    """
    win, win_pad = _windows(payload, nbits)
    params = _params(nbits, dc_table, ac_table)
    with obs.h2d(params, win_pad):
        params_d = jnp.asarray(params)
        win_d = jnp.asarray(win_pad.reshape(-1, kernel.LANES))
    dcw, acw = kernel.unit_words_pallas(params_d, win_d,
                                        interpret=interpret)
    obs.launched("unpack", dcw)
    staged = kernel.stage_tiles(dcw, acw)
    with obs.d2h(*staged):
        return (win,) + tuple(jax.device_get(staged))


def _unpack_staged(payload: bytes, nbits: int, n_blocks: int, dc_table,
                   ac_table, interpret: bool, tile_bits: int | None,
                   classes: tuple = rle.ONE_CLASS) -> tuple:
    """Stage on the device, then resolve the chain on the host."""
    if classes == rle.ONE_CLASS:
        win, dcw, acw, outc = stage(payload, nbits, dc_table, ac_table,
                                    interpret)
        n = dcw.size

        def get_tile(t):
            t0 = t * tile_bits          # outcomes hold absolute offsets
            return dcw[t0:], acw[t0:], outc[t0:]
        extra = ()
    else:
        # one device stage per table class: each class's unit words and
        # chain outcomes over the whole payload
        staged = [stage(payload, nbits, d, a, interpret)
                  for d, a in zip(*rle.table_sets(dc_table, ac_table))]
        win = staged[0][0]
        n = staged[0][1].size

        def get_tile(t):
            t0 = t * tile_bits
            return tuple([s[k][t0:] for s in staged] for k in (1, 2, 3))
        extra = (classes,)
    if tile_bits is None:
        tile_bits = n                   # one tile covers the payload
    with obs.route("resolve", "host", tiles=-(-(nbits + 1) // tile_bits)):
        return ref.resolve(win, nbits, n_blocks, tile_bits, get_tile,
                           *extra)
