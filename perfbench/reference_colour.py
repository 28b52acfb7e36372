"""Plain reference of the colour DCTZ codec (version 3), in float64 NumPy.

Written from the format and the standards alone; it imports nothing of
the system under test, only the grayscale reference (``reference.py``)
for the 8x8 transforms, the zig-zag order and the Huffman table helpers.

* ``component_planes``: pad by edge replication to 16x16 MCUs, JFIF 1.02
  RGB -> YCbCr (each component level-shifted by -128), 4:2:0 chroma as
  the mean of each 2x2 square.
* ``unrounded_levels`` / ``encode_levels``: per-component blockwise DCT
  over the quantisation table of its class (Annex K luminance K.1 for Y,
  chrominance K.2 for Cb and Cr, IJG-scaled), before and after rounding.
* ``interleave``: MCU order ``Y00 Y01 Y10 Y11 Cb Cr``, zig-zag per block.
* ``encode_dctz3``: the scalar JPEG run-length and Huffman coder over two
  table classes (Annex K K.3/K.5 for luma, K.4/K.6 for chroma, shared
  ids 1-4), DC predicted per component.
* ``parse_dctz3``: an independent decoder of the version-3 container.
* ``unrounded_rgb`` / ``decode_pixels``: dequantise, inverse DCT,
  IJG's h2v2 "fancy" chroma upsampling (3/4 nearest, 1/4 next, along
  each axis, edges replicated), YCbCr -> RGB, then round and clip.

Departures from T.81 and JFIF as libjpeg implements them, the same as
the program states for itself: samples are never rounded to 8 bits
between stages (only the final RGB is), the 2x2 mean and the upsampling
filter carry no integer rounding bias, and the upsampler's border is the
MCU-padded plane's. The container is ``DCTZ`` version 3, not a JFIF file.
"""

from __future__ import annotations

import struct
import zlib

import numpy as np

from perfbench import reference as ref

# ITU-T T.81 Annex K, Table K.2 (chrominance quantisation).
ANNEX_K_CHROMA = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99]] + [[99] * 8] * 4, dtype=np.float64)

# ITU-T T.81 Annex K, Tables K.4 and K.6 (chrominance DC and AC).
K4_BITS = (0, 3, 1, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0)
K4_VALS = tuple(range(12))
K6_BITS = (0, 2, 1, 2, 4, 4, 3, 4, 7, 5, 4, 4, 0, 1, 2, 119)
K6_VALS = (
    0x00, 0x01, 0x02, 0x03, 0x11, 0x04, 0x05, 0x21, 0x31, 0x06, 0x12, 0x41,
    0x51, 0x07, 0x61, 0x71, 0x13, 0x22, 0x32, 0x81, 0x08, 0x14, 0x42, 0x91,
    0xA1, 0xB1, 0xC1, 0x09, 0x23, 0x33, 0x52, 0xF0, 0x15, 0x62, 0x72, 0xD1,
    0x0A, 0x16, 0x24, 0x34, 0xE1, 0x25, 0xF1, 0x17, 0x18, 0x19, 0x1A, 0x26,
    0x27, 0x28, 0x29, 0x2A, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A, 0x43, 0x44,
    0x45, 0x46, 0x47, 0x48, 0x49, 0x4A, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58,
    0x59, 0x5A, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6A, 0x73, 0x74,
    0x75, 0x76, 0x77, 0x78, 0x79, 0x7A, 0x82, 0x83, 0x84, 0x85, 0x86, 0x87,
    0x88, 0x89, 0x8A, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9A,
    0xA2, 0xA3, 0xA4, 0xA5, 0xA6, 0xA7, 0xA8, 0xA9, 0xAA, 0xB2, 0xB3, 0xB4,
    0xB5, 0xB6, 0xB7, 0xB8, 0xB9, 0xBA, 0xC2, 0xC3, 0xC4, 0xC5, 0xC6, 0xC7,
    0xC8, 0xC9, 0xCA, 0xD2, 0xD3, 0xD4, 0xD5, 0xD6, 0xD7, 0xD8, 0xD9, 0xDA,
    0xE2, 0xE3, 0xE4, 0xE5, 0xE6, 0xE7, 0xE8, 0xE9, 0xEA, 0xF2, 0xF3, 0xF4,
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA)
SHARED_TABLES = {**ref.SHARED_TABLES, 3: (K4_BITS, K4_VALS),
                 4: (K6_BITS, K6_VALS)}

# component records: (id, H << 4 | V, quantisation class, table class)
COMPONENTS = ((1, 0x22, 0, 0), (2, 0x11, 1, 1), (3, 0x11, 1, 1))
BLOCK_COMPONENT = (0, 0, 0, 0, 1, 2)       # Y00 Y01 Y10 Y11 Cb Cr
BLOCK_CLASS = (0, 0, 0, 0, 1, 1)

_HEADER = struct.Struct("<4sBBBBIIBBHII")


class StreamError(ref.StreamError):
    """The reference decoder rejects a colour stream."""


def qtables(quality: int) -> tuple:
    """(luma, chroma) IJG-scaled quantisation tables, (8, 8) each."""
    q = int(min(max(quality, 1), 100))
    scale = 5000.0 / q if q < 50 else 200.0 - 2.0 * q
    return tuple(np.clip(np.floor((t * scale + 50.0) / 100.0), 1.0, 255.0)
                 for t in (ref.ANNEX_K_LUMA, ANNEX_K_CHROMA))


def mcu_grid(height: int, width: int) -> tuple:
    return -(-height // 16), -(-width // 16)


def pad16(rgb: np.ndarray) -> np.ndarray:
    h, w = rgb.shape[:2]
    return np.pad(rgb, ((0, (-h) % 16), (0, (-w) % 16), (0, 0)), mode="edge")


def component_planes(img: np.ndarray) -> list:
    """(H, W, 3) RGB -> [Y, Cb, Cr] level-shifted planes: Y at the padded
    size, Cb and Cr at half of it."""
    x = pad16(np.asarray(img)).astype(np.float64)
    r, g, b = x[..., 0], x[..., 1], x[..., 2]
    y = 0.299 * r + 0.587 * g + 0.114 * b - 128.0
    cb = -0.1687 * r - 0.3313 * g + 0.5 * b
    cr = 0.5 * r - 0.4187 * g - 0.0813 * b
    h, w = y.shape

    def mean2x2(p):
        return p.reshape(h // 2, 2, w // 2, 2).mean(axis=(1, 3))
    return [y, mean2x2(cb), mean2x2(cr)]


def unrounded_levels(img, quality: int, transform: str = "exact") -> list:
    """Per component, (gh, gw, 8, 8) DCT coefficients over their steps."""
    tables = qtables(quality)
    return [ref.forward(ref.to_blocks(p), transform)
            / tables[COMPONENTS[c][2]]
            for c, p in enumerate(component_planes(img))]


def encode_levels(img, quality: int, transform: str = "exact") -> list:
    return [np.round(x).astype(np.int64)
            for x in unrounded_levels(img, quality, transform)]


def interleave(levels: list) -> np.ndarray:
    """[Y, Cb, Cr] (gh, gw, 8, 8) levels -> (mh*mw*6, 64) zig-zag rows."""
    y, cb, cr = (np.asarray(a).reshape(*a.shape[:2], 64) for a in levels)
    mh, mw = cb.shape[:2]
    rows = []
    for i in range(mh):
        for j in range(mw):
            rows += [y[2 * i, 2 * j], y[2 * i, 2 * j + 1],
                     y[2 * i + 1, 2 * j], y[2 * i + 1, 2 * j + 1],
                     cb[i, j], cr[i, j]]
    return np.asarray(rows)[:, ref._ZIGZAG]


def deinterleave(zz: np.ndarray, mh: int, mw: int) -> list:
    """Inverse of :func:`interleave`."""
    blocks = np.zeros((len(zz), 64), np.int64)
    blocks[:, ref._ZIGZAG] = zz
    blocks = blocks.reshape(mh, mw, 6, 8, 8)
    y = np.zeros((2 * mh, 2 * mw, 8, 8), np.int64)
    for k, (di, dj) in enumerate(((0, 0), (0, 1), (1, 0), (1, 1))):
        y[di::2, dj::2] = blocks[:, :, k]
    return [y, blocks[:, :, 4], blocks[:, :, 5]]


def encode_dctz3(levels: list, quality: int, transform: str,
                 shape: tuple) -> bytes:
    """[Y, Cb, Cr] levels -> a version-3 stream coded with the shared
    Annex K tables (ids 1 and 2 for luma, 3 and 4 for chroma)."""
    codes = [(ref._codes(*SHARED_TABLES[1]), ref._codes(*SHARED_TABLES[2])),
             (ref._codes(*SHARED_TABLES[3]), ref._codes(*SHARED_TABLES[4]))]
    fields, pred = [], [0, 0, 0]
    for k, row in enumerate(interleave(levels).tolist()):
        comp, cls = BLOCK_COMPONENT[k % 6], BLOCK_CLASS[k % 6]
        dc_codes, ac_codes = codes[cls]
        diff, pred[comp] = row[0] - pred[comp], row[0]
        s = abs(diff).bit_length()
        fields.append(dc_codes[s])
        if s:
            fields.append((diff if diff > 0 else diff + (1 << s) - 1, s))
        last = max((i for i in range(1, 64) if row[i]), default=0)
        run = 0
        for i in range(1, last + 1):
            v = row[i]
            if not v:
                run += 1
                continue
            while run > 15:
                fields.append(ac_codes[0xF0])
                run -= 16
            s = abs(v).bit_length()
            fields.append(ac_codes[(run << 4) | s])
            fields.append((v if v > 0 else v + (1 << s) - 1, s))
            run = 0
        if last < 63:
            fields.append(ac_codes[0x00])
    acc, nbits = 0, 0
    for code, n in fields:
        acc, nbits = (acc << n) | code, nbits + n
    pad = -nbits % 8
    acc = (acc << pad) | ((1 << pad) - 1)
    payload = acc.to_bytes((nbits + pad) // 8, "big") if nbits else b""
    tcode = {v: k for k, v in ref.TRANSFORM_CODES.items()}[transform]
    header = _HEADER.pack(b"DCTZ", 3, 0, quality, tcode, shape[0], shape[1],
                          3, 2, 0, len(payload), 0)
    body = bytes(b for c in COMPONENTS for b in c) + bytes((1, 2, 3, 4)) \
        + payload
    crc = zlib.crc32(header[4:24] + body) & 0xFFFFFFFF
    return header[:24] + struct.pack("<I", crc) + body


def parse_dctz3(data: bytes) -> tuple:
    """One version-3 stream -> (header dict, [Y, Cb, Cr] int64 levels)."""
    if len(data) < 44:
        raise StreamError("truncated header")
    (magic, version, flags, quality, tcode, h, w, n_comp, n_cls, reserved,
     nbytes, crc) = _HEADER.unpack_from(data)
    if magic != b"DCTZ" or version != 3 or flags or reserved:
        raise StreamError("bad magic, version or reserved field")
    if (n_comp, n_cls) != (3, 2) or \
            data[28:40] != bytes(b for c in COMPONENTS for b in c):
        raise StreamError("not a baseline YCbCr 4:2:0 component layout")
    if tcode not in ref.TRANSFORM_CODES or not 1 <= quality <= 100 \
            or not h * w:
        raise StreamError("bad transform, quality or shape")
    off, tables = 44, []
    for tid in data[40:44]:
        if tid == 0:
            table, off = ref._segment(data, off)
        elif tid in SHARED_TABLES:
            table = SHARED_TABLES[tid]
        else:
            raise StreamError(f"unknown table id {tid}")
        tables.append(table)
    if len(data) != off + nbytes:
        raise StreamError("stream length disagrees with payload_nbytes")
    if zlib.crc32(data[4:24] + data[28:]) & 0xFFFFFFFF != crc:
        raise StreamError("CRC mismatch")
    mh, mw = mcu_grid(h, w)
    zz = _decode_payload(data[off:], mh * mw * 6,
                         [(tables[0], tables[1]), (tables[2], tables[3])])
    hdr = {"quality": quality, "transform": ref.TRANSFORM_CODES[tcode],
           "height": h, "width": w, "version": version, "components": 3}
    return hdr, deinterleave(zz, mh, mw)


def _decode_payload(payload: bytes, n_blocks: int, classes: list
                    ) -> np.ndarray:
    """Run-length payload over two table classes -> (n, 64) zig-zag levels,
    DC integrated per component."""
    bits = np.unpackbits(np.frombuffer(payload, np.uint8))
    nbits = bits.size
    bits = np.concatenate([bits, np.ones(32, np.uint8)])
    weights = 1 << np.arange(15, -1, -1, dtype=np.int64)
    peek = (np.lib.stride_tricks.sliding_window_view(bits, 16)[:nbits + 16]
            .astype(np.int64) @ weights).tolist()
    luts = []
    for dc_table, ac_table in classes:
        if max(dc_table[1], default=0) > 15:
            raise StreamError("DC table codes a symbol above 15")
        luts.append([[a.tolist() for a in ref._lut(*t)]
                     for t in (dc_table, ac_table)])
    out = np.zeros((n_blocks, 64), np.int64)
    pos, pred = 0, [0, 0, 0]
    for b in range(n_blocks):
        (dc_sym, dc_len), (ac_sym, ac_len) = luts[BLOCK_CLASS[b % 6]]
        comp = BLOCK_COMPONENT[b % 6]
        row = out[b]
        if pos >= nbits:
            raise StreamError("payload ends mid-stream")
        win = peek[pos]
        n = dc_len[win]
        if not n:
            raise StreamError(f"no DC code at bit {pos}")
        s = dc_sym[win]
        pos += n
        diff = ref._amp(peek[pos] >> (16 - s), s) if s else 0
        pos += s
        pred[comp] += diff
        row[0] = pred[comp]
        k = 1
        while k < 64:
            win = peek[pos]
            n = ac_len[win]
            if not n:
                raise StreamError(f"no AC code at bit {pos}")
            sym = ac_sym[win]
            pos += n
            if sym == 0x00:
                break
            if sym == 0xF0:
                k += 16
                continue
            run, size = sym >> 4, sym & 15
            k += run
            if k > 63 or not size:
                raise StreamError(f"AC run past the block end at bit {pos}")
            row[k] = ref._amp(peek[pos] >> (16 - size), size)
            pos += size
            k += 1
        if pos > nbits:
            raise StreamError("payload ends mid-block")
    return out


def upsample(plane: np.ndarray) -> np.ndarray:
    """IJG h2v2 fancy upsampling: (h, w) -> (2h, 2w), edges replicated."""
    def along(x, axis):
        x = np.moveaxis(x, axis, 0)
        prev = np.concatenate([x[:1], x[:-1]])
        nxt = np.concatenate([x[1:], x[-1:]])
        out = np.empty((2 * x.shape[0],) + x.shape[1:])
        out[0::2] = 0.75 * x + 0.25 * prev
        out[1::2] = 0.75 * x + 0.25 * nxt
        return np.moveaxis(out, 0, axis)
    return along(along(plane, 0), 1)


def unrounded_rgb(levels: list, quality: int,
                  transform: str = "exact") -> np.ndarray:
    """[Y, Cb, Cr] levels -> (H16, W16, 3) RGB before rounding, clipped
    to [0, 255]."""
    tables = qtables(quality)
    y, cb, cr = (ref.from_blocks(ref.inverse(
        np.asarray(lv, np.float64) * tables[COMPONENTS[c][2]], transform))
        for c, lv in enumerate(levels))
    y = y + 128.0
    cb, cr = upsample(cb), upsample(cr)
    rgb = np.stack([y + 1.402 * cr, y - 0.34414 * cb - 0.71414 * cr,
                    y + 1.772 * cb], axis=-1)
    return np.clip(rgb, 0.0, 255.0)


def decode_pixels(levels: list, quality: int, shape: tuple,
                  transform: str = "exact") -> np.ndarray:
    """Levels -> (H, W, 3) uint8 reconstruction cropped to ``shape``."""
    rgb = np.round(unrounded_rgb(levels, quality, transform))
    return rgb.astype(np.uint8)[:shape[0], :shape[1]]
