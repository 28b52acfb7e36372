"""The ``DCTZ`` container: a versioned bitstream around the entropy stage.

Layout (all integers little-endian; full spec in docs/bitstream.md)::

    offset size field
    0      4    magic  b"DCTZ"
    4      1    version (1 or 2)
    5      1    flags (reserved, must be 0)
    6      1    quality (1..100, IJG scaling)
    7      1    transform code (0 exact / 1 cordic / 2 loeffler)
    8      4    height  u32 (original, pre-padding)
    12     4    width   u32
    16     1    dc_table_id (0 = table embedded in this stream)
    17     1    ac_table_id (0 = table embedded in this stream)
    18     2    reserved (must be 0)
    20     4    payload_nbytes u32
    24     4    crc32 over (header bytes 4..23 ‖ tables ‖ payload)
    28     ...  DC table segment, then AC table segment (embedded only)
    ...    ...  entropy-coded payload (payload_nbytes bytes)

Version 1 embeds both canonical Huffman tables (table id 0).  Version 2
adds **shared table ids** (>= 1, resolved through
:data:`repro.core.entropy.huffman.DEFAULT_TABLES`): the encoder picks,
per alphabet, whichever is cheaper — per-stream table coding bits plus
the embedded segment bytes, or the well-known shared table — and only
writes version 2 when at least one shared id is used, so fully-embedded
streams stay byte-identical to version 1.  Decoders reject unknown
magic/version/transform/table ids and trailing bytes; within a version
the format evolves by replacement, not extension.

Version 3 is a **colour** stream: baseline YCbCr 4:2:0
(:mod:`repro.core.colour`).  Bytes 16 and 17 hold the component count
(3) and the table-class count (2); after the 28-byte header come one
4-byte record per component (id, sampling ``H << 4 | V``, quantisation
class, table class: Y ``1, 0x22, 0, 0``, Cb ``2, 0x11, 1, 1``, Cr ``3,
0x11, 1, 1``), then the (DC id, AC id) pair of each table class, then
the embedded segments (class 0 DC, class 0 AC, class 1 DC, class 1 AC,
each only where its id is 0), then the payload: MCUs in raster order,
each ``Y00 Y01 Y10 Y11 Cb Cr``, DC predicted per component.  The CRC
covers the records and ids like the tables.

This module is importable without jax: the host halves
(:func:`encode_zigzag_host` / :func:`decode_zigzag_host`) are pure
NumPy, which the engine's thread pool runs per stream; only the
qcoeff/image entry points pull in the array stack, lazily.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

from repro import obs
from repro.core.entropy import bitio, dense, huffman, rle

MAGIC = b"DCTZ"
VERSION_EMBEDDED = 1        # both tables embedded (the v1 layout)
VERSION_SHARED = 2          # at least one shared table id
VERSION_COLOUR = 3          # YCbCr 4:2:0, two table classes
SUPPORTED_VERSIONS = (VERSION_EMBEDDED, VERSION_SHARED, VERSION_COLOUR)
VERSION = VERSION_COLOUR    # newest version this module writes/reads
TABLE_EMBEDDED = 0

TABLE_MODES = ("auto", "embedded", "shared")

_HEADER = struct.Struct("<4sBBBBIIBBHII")
HEADER_NBYTES = _HEADER.size            # 28

# version 3: (component id, H << 4 | V, quantisation class, table class)
COLOUR_COMPONENTS = ((1, 0x22, 0, 0), (2, 0x11, 1, 1), (3, 0x11, 1, 1))
COLOUR_TABLE_CLASSES = 2
# the per-component records and the (DC id, AC id) pair of each class
_COLOUR_LAYOUT = bytes(b for c in COLOUR_COMPONENTS for b in c)
COLOUR_HEADER_NBYTES = (HEADER_NBYTES + len(_COLOUR_LAYOUT)
                        + 2 * COLOUR_TABLE_CLASSES)     # 44
# per block of an MCU (Y00 Y01 Y10 Y11 Cb Cr): component and table class
COLOUR_BLOCK_COMPONENTS = (0, 0, 0, 0, 1, 2)
COLOUR_BLOCK_CLASSES = tuple(COLOUR_COMPONENTS[c][3]
                             for c in COLOUR_BLOCK_COMPONENTS)

TRANSFORM_CODES = {"exact": 0, "cordic": 1, "loeffler": 2}
_TRANSFORM_NAMES = {v: k for k, v in TRANSFORM_CODES.items()}


class BitstreamError(ValueError):
    """A ``DCTZ`` stream is malformed: bad magic/version/field values,
    truncated data, CRC mismatch, or an invalid entropy payload."""


def _grid_shape(height: int, width: int) -> tuple:
    return (height + 7) // 8, (width + 7) // 8


def _check_encode_args(quality: int, transform: str, tables: str) -> None:
    if transform not in TRANSFORM_CODES:
        raise ValueError(f"unknown transform {transform!r}; "
                         f"expected one of {sorted(TRANSFORM_CODES)}")
    if not 1 <= int(quality) <= 100:
        raise ValueError(f"quality {quality} outside [1, 100]")
    if tables not in TABLE_MODES:
        raise ValueError(f"unknown tables mode {tables!r}; "
                         f"expected one of {TABLE_MODES}")


def encode_qcoeffs(qcoeffs, quality: int, transform: str,
                   orig_shape: tuple, *, tables: str = "auto",
                   packer=None, symbolizer=None) -> bytes:
    """Entropy-code one image's quantised levels into a ``DCTZ`` stream.

    Args:
        qcoeffs: (gh, gw, 8, 8) int quantised levels, raster block
            order; ``(gh, gw)`` must equal the block grid of
            ``orig_shape`` padded to 8.
        quality: JPEG quality factor in [1, 100] (stored so the decoder
            rebuilds the same quantisation table).
        transform: encoder transform name (see
            :data:`TRANSFORM_CODES`); stored for provenance and for
            ``mode="matched"`` decodes.
        orig_shape: (H, W) of the image before block padding.
        tables: Huffman table policy — "auto" (per alphabet, shared
            table when it beats embedded cost), "embedded" (always
            per-stream tables: the version-1 layout, byte-identical to
            pre-v2 encoders), or "shared" (force the shared ids; raises
            if the stream needs a symbol they cannot code).
        packer: bit-packing backend override, a ``(fields, widths) ->
            bytes`` callable (e.g. the routed
            :func:`repro.kernels.pack_bits.pack_bits`); None = the
            NumPy reference.
        symbolizer: symbolisation route override (see
            :func:`_frame_stream`), e.g. what
            :func:`repro.kernels.symbolize.make_symbolizer` returns on a
            TPU; None = the host symbolizer
            (:func:`repro.core.entropy.dense.prepare`).  Bytes identical
            either way.

    Returns:
        The complete container as bytes.

    Raises:
        ValueError: shape/quality/transform/tables out of range, a
            level too large for a 15-bit amplitude
            (:class:`repro.core.entropy.rle.RangeError`), or
            ``tables="shared"`` with an uncoverable symbol stream.
    """
    import jax.numpy as jnp

    from repro.core.entropy import scan
    h, w = int(orig_shape[0]), int(orig_shape[1])
    _check_encode_args(quality, transform, tables)
    gh, gw = _grid_shape(h, w)
    qcoeffs = jnp.asarray(qcoeffs)
    if qcoeffs.shape != (gh, gw, 8, 8):
        raise ValueError(f"qcoeffs shape {qcoeffs.shape} does not match "
                         f"the {gh}x{gw} block grid of a {h}x{w} image")

    # accelerated half: zig-zag + DC differential (jnp, vmappable)
    z = scan.block_stream(qcoeffs)
    dc_diff, ac = scan.dc_differential(z)
    return _frame_stream(np.asarray(dc_diff), np.asarray(ac),
                         quality, transform, h, w, tables=tables,
                         packer=packer, symbolizer=symbolizer)


def encode_zigzag_host(z: np.ndarray, quality: int, transform: str,
                       orig_shape: tuple, *, tables: str = "auto",
                       packer=None, symbolizer=None) -> bytes:
    """Entropy-code a (n_blocks, 64) zig-zag stream — pure host path.

    The jax-free sibling of :func:`encode_qcoeffs` for callers that
    already ran the zig-zag scan on the device for a whole batch (the
    engine's overlapped ``to_bytes_list``): everything here — DC
    differential, symbolisation, tables, packing, framing — is NumPy,
    so worker threads never contend on jax dispatch and release the GIL
    inside the array ops.  Bytes are identical to
    :func:`encode_qcoeffs` on the same blocks.

    Args:
        z: (gh*gw, 64) int zig-zag stream in raster block order (as
            produced by :func:`repro.core.entropy.scan.block_stream`).
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform name (see
            :data:`TRANSFORM_CODES`).
        orig_shape: (H, W) of the image before block padding.
        tables: Huffman table policy, as in :func:`encode_qcoeffs`.
        packer: bit-packing backend override, as in
            :func:`encode_qcoeffs`.
        symbolizer: symbolisation backend override, as in
            :func:`encode_qcoeffs`.  The default keeps this function's
            no-jax-import property.

    Returns:
        The complete container as bytes.

    Raises:
        ValueError: shape/quality/transform/tables out of range, or a
            level too large for a 15-bit amplitude.
    """
    h, w = int(orig_shape[0]), int(orig_shape[1])
    _check_encode_args(quality, transform, tables)
    gh, gw = _grid_shape(h, w)
    z = np.asarray(z)
    if z.shape != (gh * gw, 64):
        raise ValueError(f"zig-zag stream shape {z.shape} does not match "
                         f"the {gh}x{gw} block grid of a {h}x{w} image")
    dc = z[:, 0].astype(np.int64)
    dc_diff = np.diff(dc, prepend=np.int64(0))
    return _frame_stream(dc_diff, z[:, 1:], quality, transform, h, w,
                         tables=tables, packer=packer,
                         symbolizer=symbolizer)


def _colour_grid(height: int, width: int) -> tuple:
    """(MCU rows, MCU columns) of a colour image: 16x16 MCUs."""
    return (height + 15) // 16, (width + 15) // 16


def _component_index(n_blocks: int) -> list:
    """Block indices of each component of an interleaved colour stream."""
    comp = rle.block_classes(COLOUR_BLOCK_COMPONENTS, n_blocks)
    return [np.flatnonzero(comp == c) for c in range(len(COLOUR_COMPONENTS))]


def colour_dc_diff(dc: np.ndarray) -> np.ndarray:
    """(n,) DC levels of an interleaved colour stream -> DC differences,
    each block predicted from the previous block of its own component
    (0 for a component's first block)."""
    dc = np.asarray(dc, np.int64)
    out = np.empty_like(dc)
    for idx in _component_index(dc.size):
        out[idx] = np.diff(dc[idx], prepend=np.int64(0))
    return out


def colour_dc_integrate(dc_diff: np.ndarray) -> np.ndarray:
    """Invert :func:`colour_dc_diff`: (n,) differences -> (n,) DC levels."""
    dc_diff = np.asarray(dc_diff, np.int64)
    out = np.empty_like(dc_diff)
    for idx in _component_index(dc_diff.size):
        out[idx] = np.cumsum(dc_diff[idx])
    return out


def encode_colour_zigzag_host(z: np.ndarray, quality: int, transform: str,
                              orig_shape: tuple, *, tables: str = "auto",
                              packer=None, symbolizer=None) -> bytes:
    """Entropy-code one colour image's interleaved zig-zag stream into a
    ``DCTZ`` version-3 stream — pure host path.

    Args:
        z: (mh*mw*6, 64) int zig-zag levels, MCUs in raster order, each
            ``Y00 Y01 Y10 Y11 Cb Cr`` (as produced by
            :func:`repro.core.colour.compress_batch_mcus`).
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform name (see :data:`TRANSFORM_CODES`).
        orig_shape: (H, W) of the image before MCU padding.
        tables: Huffman table policy, as in :func:`encode_qcoeffs`,
            applied per table class (luma: shared ids 1 and 2; chroma:
            shared ids 3 and 4).
        packer: bit-packing backend override, as in
            :func:`encode_qcoeffs`.
        symbolizer: symbolisation backend override, as in
            :func:`encode_qcoeffs`; called with ``classes=``.

    Returns:
        The complete container as bytes.

    Raises:
        ValueError: shape/quality/transform/tables out of range, or a
            level too large for a 15-bit amplitude.
    """
    h, w = int(orig_shape[0]), int(orig_shape[1])
    _check_encode_args(quality, transform, tables)
    mh, mw = _colour_grid(h, w)
    z = np.asarray(z)
    n = mh * mw * len(COLOUR_BLOCK_CLASSES)
    if z.shape != (n, 64):
        raise ValueError(f"zig-zag stream shape {z.shape} does not match "
                         f"the {mh}x{mw} MCU grid of a {h}x{w} image")
    classes = COLOUR_BLOCK_CLASSES
    prep = (symbolizer or dense.prepare)(
        colour_dc_diff(z[:, 0]), z[:, 1:], packer=packer, classes=classes)
    with obs.span("entropy.tables"):
        chosen = [(_choose_table(prep.dc_freq[c], dc_sid, tables, "DC"),
                   _choose_table(prep.ac_freq[c], ac_sid, tables, "AC"))
                  for c, (dc_sid, ac_sid) in enumerate(huffman.STANDARD_IDS)]
    with obs.span("entropy.payload"):
        payload = prep.payload(tuple(dc[1] for dc, _ in chosen),
                               tuple(ac[1] for _, ac in chosen))
    with obs.span("entropy.frame"):
        ids = bytes(i for dc, ac in chosen for i in (dc[0], ac[0]))
        segs = b"".join(t.to_segment() for pair in chosen
                        for tid, t in pair if tid == TABLE_EMBEDDED)
        header = _HEADER.pack(MAGIC, VERSION_COLOUR, 0, int(quality),
                              TRANSFORM_CODES[transform], h, w,
                              len(COLOUR_COMPONENTS), COLOUR_TABLE_CLASSES,
                              0, len(payload), 0)
        body = _COLOUR_LAYOUT + ids + segs + payload
        crc = zlib.crc32(header[4:24] + body) & 0xFFFFFFFF
        return header[:24] + struct.pack("<I", crc) + body


def _choose_table(freqs: np.ndarray, shared_id: int, tables: str,
                  what: str) -> tuple:
    """Pick (table_id, table) for one alphabet under the table policy.

    "auto" compares total Huffman bits: the per-stream table costs its
    coded bits plus 8x its embedded segment bytes; the shared table
    costs its coded bits alone (or is unusable when the stream needs a
    symbol it lacks).  Amplitude bits cancel.  The rule is
    deterministic, so re-encoding a decoded stream reproduces it.
    Forcing "shared" skips the per-stream table build entirely — the
    streaming fast path; "auto" still builds it (memoised on the
    histogram) because the comparison needs its coded bits.
    """
    if tables == "shared":
        shared = huffman.DEFAULT_TABLES.get(shared_id)
        if huffman.coded_bits(shared, freqs) is None:
            raise ValueError(
                f"{what} stream needs a symbol the shared table id "
                f"{shared_id} cannot code; use tables='auto' or "
                f"'embedded'")
        return shared_id, shared
    embedded = huffman.build_table_memo(freqs)
    if tables == "embedded":
        return TABLE_EMBEDDED, embedded
    shared = huffman.DEFAULT_TABLES.get(shared_id)
    shared_bits = huffman.coded_bits(shared, freqs)
    embedded_cost = (huffman.coded_bits(embedded, freqs)
                     + 8 * len(embedded.to_segment()))
    if shared_bits is not None and shared_bits < embedded_cost:
        return shared_id, shared
    return TABLE_EMBEDDED, embedded


def _frame_stream(dc_diff: np.ndarray, ac: np.ndarray, quality: int,
                  transform: str, h: int, w: int, *,
                  tables: str = "auto", packer=None,
                  symbolizer=None) -> bytes:
    """Host edge shared by both encoders: the staged entropy pipeline
    (symbolise -> table choice -> codeword lookup -> routed packing)
    plus framing.

    ``symbolizer`` routes the symbolisation/payload stages: a
    ``(dc_diff, ac, packer=None) -> prepared`` callable whose result
    exposes ``dc_freq``/``ac_freq`` histograms (consumed by table
    choice below) and ``payload(dc_table, ac_table) -> bytes``.
    ``None`` is the host symbolizer, :func:`repro.core.entropy.dense.
    prepare`; the device route (what
    :func:`repro.kernels.symbolize.make_symbolizer` returns on a TPU)
    gives identical bytes (CI-gated), so the table negotiation and
    framing here never change.
    """
    prep = (symbolizer or dense.prepare)(dc_diff, ac, packer=packer)
    with obs.span("entropy.tables"):
        dc_id, dc_table = _choose_table(prep.dc_freq,
                                        huffman.STANDARD_DC_LUMA_ID,
                                        tables, "DC")
        ac_id, ac_table = _choose_table(prep.ac_freq,
                                        huffman.STANDARD_AC_LUMA_ID,
                                        tables, "AC")
    with obs.span("entropy.payload"):
        payload = prep.payload(dc_table, ac_table)

    with obs.span("entropy.frame"):
        table_segs = b""
        if dc_id == TABLE_EMBEDDED:
            table_segs += dc_table.to_segment()
        if ac_id == TABLE_EMBEDDED:
            table_segs += ac_table.to_segment()
        # fully-embedded streams keep the version-1 byte layout so pre-v2
        # decoders (and the golden fixtures) are untouched
        version = (VERSION_EMBEDDED
                   if dc_id == ac_id == TABLE_EMBEDDED else VERSION_SHARED)
        header = _HEADER.pack(MAGIC, version, 0, int(quality),
                              TRANSFORM_CODES[transform], h, w,
                              dc_id, ac_id, 0, len(payload), 0)
        # CRC protects every header field after the magic (a flipped
        # quality or shape byte must not decode plausibly) plus tables
        # and payload
        crc = zlib.crc32(header[4:24] + table_segs + payload) & 0xFFFFFFFF
        return header[:24] + struct.pack("<I", crc) + table_segs + payload


def read_header(data: bytes) -> dict:
    """Parse and validate the fixed 28-byte header.

    Args:
        data: at least the first 28 bytes of a stream.

    Returns:
        Dict with ``version``, ``quality``, ``transform``, ``height``,
        ``width``, ``dc_table_id``, ``ac_table_id``, ``payload_nbytes``,
        ``crc32``.  A version-3 (colour) stream, whose component records
        and table ids follow the fixed header, gives ``components`` (3)
        and ``table_ids`` (one (DC id, AC id) pair per table class) in
        place of the two table ids.

    Raises:
        BitstreamError: short data, bad magic, unsupported version,
            or any field outside its valid range — including a table id
            the version does not define (version 1 allows only
            embedded; versions 2 and 3 also allow registered shared
            ids) and a version-3 component layout other than baseline
            YCbCr 4:2:0.
    """
    if len(data) < HEADER_NBYTES:
        raise BitstreamError(
            f"truncated header: got {len(data)} bytes, need "
            f"{HEADER_NBYTES}")
    (magic, version, flags, quality, tcode, height, width,
     dc_id, ac_id, reserved, payload_nbytes, crc) = _HEADER.unpack_from(
        data)
    if magic != MAGIC:
        raise BitstreamError(f"not a DCTZ stream (magic {magic!r})")
    if version not in SUPPORTED_VERSIONS:
        raise BitstreamError(
            f"unsupported DCTZ version {version}; this decoder reads "
            f"versions {SUPPORTED_VERSIONS}")
    if flags != 0 or reserved != 0:
        raise BitstreamError("reserved header fields must be zero")
    if tcode not in _TRANSFORM_NAMES:
        raise BitstreamError(f"unknown transform code {tcode}")
    if not 1 <= quality <= 100:
        raise BitstreamError(f"quality {quality} outside [1, 100]")
    if height == 0 or width == 0:
        raise BitstreamError("zero image dimension")
    hdr = {"version": version, "quality": quality,
           "transform": _TRANSFORM_NAMES[tcode],
           "height": height, "width": width}
    if version == VERSION_COLOUR:
        if (dc_id, ac_id) != (len(COLOUR_COMPONENTS), COLOUR_TABLE_CLASSES):
            raise BitstreamError(
                f"unsupported colour layout: {dc_id} components in {ac_id} "
                f"table classes (version {VERSION_COLOUR} codes 3 in 2)")
        if len(data) < COLOUR_HEADER_NBYTES:
            raise BitstreamError(
                f"truncated header: got {len(data)} bytes, need "
                f"{COLOUR_HEADER_NBYTES}")
        if data[HEADER_NBYTES:HEADER_NBYTES + len(_COLOUR_LAYOUT)] != \
                _COLOUR_LAYOUT:
            raise BitstreamError(
                "unsupported component records: version 3 codes baseline "
                "YCbCr 4:2:0 (Y 2x2 luma class, Cb and Cr 1x1 chroma)")
        ids = data[COLOUR_HEADER_NBYTES - 2 * COLOUR_TABLE_CLASSES:
                   COLOUR_HEADER_NBYTES]
        for tid in ids:
            if tid != TABLE_EMBEDDED and not huffman.DEFAULT_TABLES.known(
                    tid):
                raise BitstreamError(
                    f"unknown table ids {tuple(ids)}; version 3 defines "
                    f"embedded (id 0) and registered shared ids "
                    f"{huffman.DEFAULT_TABLES.ids()}")
        return dict(hdr, components=len(COLOUR_COMPONENTS),
                    table_ids=((ids[0], ids[1]), (ids[2], ids[3])),
                    payload_nbytes=payload_nbytes, crc32=crc)
    for tid in (dc_id, ac_id):
        if tid == TABLE_EMBEDDED:
            continue
        if version == VERSION_EMBEDDED:
            raise BitstreamError(
                f"unknown table ids ({dc_id}, {ac_id}); only embedded "
                f"tables (id {TABLE_EMBEDDED}) are defined in version "
                f"{VERSION_EMBEDDED}")
        if not huffman.DEFAULT_TABLES.known(tid):
            raise BitstreamError(
                f"unknown table ids ({dc_id}, {ac_id}); version "
                f"{VERSION_SHARED} defines embedded (id 0) and "
                f"registered shared ids {huffman.DEFAULT_TABLES.ids()}")
    return dict(hdr, dc_table_id=dc_id, ac_table_id=ac_id,
                payload_nbytes=payload_nbytes, crc32=crc)


def _resolve_tables(data: bytes, hdr: dict) -> tuple:
    """(dc_table, ac_table, payload_offset): embedded segments are
    parsed from the stream (DC first), shared ids resolve through the
    default registry (``read_header`` already vetted the ids).  For a
    colour stream the two tables are tuples, one table per class."""
    if hdr["version"] == VERSION_COLOUR:
        ids, off = hdr["table_ids"], COLOUR_HEADER_NBYTES
    else:
        ids, off = ((hdr["dc_table_id"], hdr["ac_table_id"]),), \
            HEADER_NBYTES
    out = []
    try:
        for tid in (t for pair in ids for t in pair):
            if tid == TABLE_EMBEDDED:
                table, off = huffman.CanonicalTable.from_segment(data, off)
            else:
                table = huffman.DEFAULT_TABLES.get(tid)
            out.append(table)
    except huffman.InvalidTable as e:
        raise BitstreamError(f"bad embedded Huffman table: {e}") from e
    if hdr["version"] == VERSION_COLOUR:
        return tuple(out[0::2]), tuple(out[1::2]), off
    return out[0], out[1], off


def verify_crc(data: bytes) -> bool:
    """Check a stream's CRC without entropy-decoding the payload.

    Parses the header and table segments only (to locate the payload
    extent), then recomputes the CRC the way the writer does.  Used by
    ``dctz_cli info`` to report integrity cheaply.

    Returns:
        True iff the framing lengths agree and the CRC matches.

    Raises:
        BitstreamError: the header itself is invalid (there is no CRC
            to check against).
    """
    hdr = read_header(data)
    try:
        _, _, off = _resolve_tables(data, hdr)
    except BitstreamError:
        return False
    end = off + hdr["payload_nbytes"]
    if len(data) != end:
        return False
    crc = zlib.crc32(data[4:24] + data[HEADER_NBYTES:end]) & 0xFFFFFFFF
    return crc == hdr["crc32"]


def decode_zigzag_host(data: bytes, *, unpacker=None) -> tuple:
    """Parse + entropy-decode a stream to its zig-zag form — pure host.

    The jax-free half of :func:`decode_qcoeffs`: framing validation,
    CRC, table resolution (embedded segments or shared registry ids),
    the LUT entropy decode and the (integer, bit-exact) DC integration
    all run in NumPy, so the engine's ``decode_batch`` can fan streams
    across threads without contending on jax dispatch; only the inverse
    zig-zag permutation is left for the device.

    Args:
        data: one complete ``DCTZ`` stream (version 1, 2 or 3).
        unpacker: optional payload-decode backend handed through to
            :func:`repro.core.entropy.rle.decode_payload` — e.g. the
            routed :func:`repro.kernels.unpack_bits.unpack_bits` for a
            device-resident decode.  ``None`` keeps the jax-free LUT
            walk (and with it this function's no-jax-import property).

    Returns:
        ``(z, header)``: the (gh*gw, 64) int32 zig-zag stream in raster
        block order and the parsed header dict; for a colour stream the
        (mh*mw*6, 64) interleaved stream, MCUs in raster order.

    Raises:
        BitstreamError: any malformation — truncation (header, tables or
            payload), trailing bytes, CRC mismatch, invalid table
            segments or ids, or an undecodable entropy payload.
    """
    with obs.span("entropy.parse"):
        hdr = read_header(data)
        dc_table, ac_table, off = _resolve_tables(data, hdr)
        end = off + hdr["payload_nbytes"]
        if len(data) < end:
            raise BitstreamError(
                f"truncated payload: stream has {len(data) - off} of "
                f"{hdr['payload_nbytes']} declared bytes")
        if len(data) > end:
            raise BitstreamError(f"{len(data) - end} trailing bytes after "
                                 f"the declared payload")
        crc = zlib.crc32(data[4:24] + data[HEADER_NBYTES:end]) & 0xFFFFFFFF
        if crc != hdr["crc32"]:
            raise BitstreamError(
                f"CRC mismatch: header says {hdr['crc32']:#010x}, stream "
                f"hashes to {crc:#010x} (corrupted stream)")

    colour = hdr["version"] == VERSION_COLOUR
    if colour:
        mh, mw = _colour_grid(hdr["height"], hdr["width"])
        n_blocks = mh * mw * len(COLOUR_BLOCK_CLASSES)
        extra = {"classes": COLOUR_BLOCK_CLASSES}
    else:
        gh, gw = _grid_shape(hdr["height"], hdr["width"])
        n_blocks, extra = gh * gw, {}
    # every block costs at least 2 payload bits (DC code + EOB), so a
    # shape whose block count exceeds 4 bytes^-1 * payload is invalid —
    # this bounds allocation before trusting the header's dimensions
    if n_blocks > 4 * hdr["payload_nbytes"]:
        raise BitstreamError(
            f"declared {hdr['height']}x{hdr['width']} image needs "
            f"{n_blocks} blocks but the {hdr['payload_nbytes']}-byte "
            f"payload cannot hold them (corrupted shape)")
    try:
        dc_diff, ac = rle.decode_payload(data[off:end], n_blocks,
                                         dc_table, ac_table,
                                         unpacker=unpacker, **extra)
    except (bitio.TruncatedStream, ValueError) as e:
        raise BitstreamError(f"bad entropy payload: {e}") from e

    # DC integration is integer-exact, so the host cumsum matches the
    # device's scan.dc_integrate bit for bit
    z = np.empty((n_blocks, 64), dtype=np.int32)
    z[:, 0] = (colour_dc_integrate(dc_diff) if colour
               else np.cumsum(dc_diff, dtype=np.int64))
    z[:, 1:] = ac
    return z, hdr


def stream_layout(data: bytes) -> tuple:
    """``(components, mcus)`` of a stream from its header alone:
    ``(1, blocks)`` for grayscale, ``(3, MCUs)`` for colour, ``(0, 0)``
    where the header does not parse (the decode then says why)."""
    try:
        hdr = read_header(data)
    except BitstreamError:
        return 0, 0
    if hdr["version"] == VERSION_COLOUR:
        mh, mw = _colour_grid(hdr["height"], hdr["width"])
        return hdr["components"], mh * mw
    gh, gw = _grid_shape(hdr["height"], hdr["width"])
    return 1, gh * gw


def decode_qcoeffs(data: bytes, *, unpacker=None) -> tuple:
    """Full inverse of :func:`encode_qcoeffs`.

    Args:
        data: one complete ``DCTZ`` stream.
        unpacker: optional payload-decode backend (see
            :func:`decode_zigzag_host`).

    Returns:
        ``(qcoeffs, header)``: the (gh, gw, 8, 8) int32 quantised levels
        and the parsed header dict.

    Raises:
        BitstreamError: any malformation — truncation (header, tables or
            payload), trailing bytes, CRC mismatch, invalid table
            segments or ids, or an undecodable entropy payload.
    """
    import jax.numpy as jnp

    from repro.core.entropy import scan
    z, hdr = decode_zigzag_host(data, unpacker=unpacker)
    if hdr["version"] == VERSION_COLOUR:
        raise ValueError("a colour (version 3) stream has no single block "
                         "grid: decode it with decode_image or "
                         "codec_engine.decode_batch")
    gh, gw = _grid_shape(hdr["height"], hdr["width"])
    # accelerated half of the inverse: the inverse zig-zag permutation
    return scan.unblock_stream(jnp.asarray(z), gh, gw), hdr


def encode_image(img, quality: int = 50, transform: str = "exact",
                 cordic_config=None, *, tables: str = "auto") -> bytes:
    """Compress a (H, W) grayscale image to a complete ``DCTZ`` stream,
    or an (H, W, 3) RGB image to a version-3 colour stream
    (:func:`repro.core.colour.encode_image`).

    The array half (DCT + quantise + zig-zag) runs the same jitted path
    as :func:`repro.core.codec.compress`; only bit packing happens on
    the host.

    Args:
        img: (H, W) uint8/float grayscale or (H, W, 3) RGB image.
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform ("exact"/"cordic"/"loeffler").
        cordic_config: CORDIC config for ``transform == "cordic"``
            (None = the paper's config).
        tables: Huffman table policy (see :func:`encode_qcoeffs`).

    Returns:
        The container bytes; ``len()`` of it is the *measured* size the
        rate–distortion benches report.
    """
    from repro.core import codec, colour, cordic
    if colour.is_colour(img):
        return colour.encode_image(img, quality, transform, cordic_config,
                                   tables=tables)
    c = codec.compress(img, quality, transform,
                       cordic_config or cordic.PAPER_CONFIG)
    return c.to_bytes(tables=tables)


def decode_image(data: bytes, mode: str = "standard", *, unpacker=None):
    """Reconstruct the (H, W) uint8 image from a ``DCTZ`` stream.

    The entropy stage is lossless over the quantised levels, so the
    result is bit-exact with decoding the in-memory
    :class:`repro.core.codec.CompressedImage` the encoder started from.

    Args:
        data: one complete ``DCTZ`` stream.
        mode: "standard" (exact IDCT — a decoder that ignores the
            encoder's approximate transform) or "matched" (the adjoint
            of the stored transform, with the paper's CORDIC config).
        unpacker: optional payload-decode backend (see
            :func:`decode_zigzag_host`), e.g.
            ``repro.kernels.unpack_bits.make_unpacker()`` on a TPU.

    Returns:
        (H, W) uint8 reconstruction, cropped to the stored shape; (H, W,
        3) uint8 RGB for a colour stream.

    Raises:
        BitstreamError: see :func:`decode_qcoeffs`.
    """
    from repro.core import codec, colour
    if read_header(data)["version"] == VERSION_COLOUR:
        z, hdr = decode_zigzag_host(data, unpacker=unpacker)
        return colour.decompress(
            z, hdr["height"], hdr["width"], hdr["quality"],
            "exact" if mode == "standard" else hdr["transform"])
    c = codec.CompressedImage.from_bytes(data, unpacker=unpacker)
    return codec.decompress(c, mode=mode)
