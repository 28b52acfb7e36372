"""Plain reference of the DCTZ image codec, in float64 NumPy.

Written from the format and the paper's transforms alone: it imports
nothing of the system under test and uses no table the system builds.

* ``encode_levels``: pad by edge replication, level shift, blockwise
  8x8 DCT (the orthonormal matrix form, or the Cordic-Loeffler graph of
  arXiv:1306.1373 with the paper's CORDIC budget), JPEG quantisation at
  the IJG-scaled Annex K luminance table.
* ``decode_pixels``: dequantise, inverse transform, +128, round, clip.
* ``parse_dctz``: an independent decoder of the ``DCTZ`` container
  (header, CRC, Huffman tables embedded or the T.81 Annex K shared
  ones, JPEG run-length payload) back to quantised levels.
* ``psnr``: paper eq. (23), peak = the original image's maximum.
"""

from __future__ import annotations

import math
import struct
import zlib

import numpy as np

# ITU-T T.81 Annex K, Table K.1 (luminance quantisation).
ANNEX_K_LUMA = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99]], dtype=np.float64)

# ITU-T T.81 Annex K, Tables K.3 and K.5 (BITS / HUFFVAL), the shared
# table ids 1 and 2 of DCTZ version 2.
K3_BITS = (0, 1, 5, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0, 0, 0, 0)
K3_VALS = tuple(range(12))
K5_BITS = (0, 2, 1, 3, 3, 2, 4, 3, 5, 5, 4, 4, 0, 0, 1, 125)
K5_VALS = (
    0x01, 0x02, 0x03, 0x00, 0x04, 0x11, 0x05, 0x12, 0x21, 0x31, 0x41, 0x06,
    0x13, 0x51, 0x61, 0x07, 0x22, 0x71, 0x14, 0x32, 0x81, 0x91, 0xA1, 0x08,
    0x23, 0x42, 0xB1, 0xC1, 0x15, 0x52, 0xD1, 0xF0, 0x24, 0x33, 0x62, 0x72,
    0x82, 0x09, 0x0A, 0x16, 0x17, 0x18, 0x19, 0x1A, 0x25, 0x26, 0x27, 0x28,
    0x29, 0x2A, 0x34, 0x35, 0x36, 0x37, 0x38, 0x39, 0x3A, 0x43, 0x44, 0x45,
    0x46, 0x47, 0x48, 0x49, 0x4A, 0x53, 0x54, 0x55, 0x56, 0x57, 0x58, 0x59,
    0x5A, 0x63, 0x64, 0x65, 0x66, 0x67, 0x68, 0x69, 0x6A, 0x73, 0x74, 0x75,
    0x76, 0x77, 0x78, 0x79, 0x7A, 0x83, 0x84, 0x85, 0x86, 0x87, 0x88, 0x89,
    0x8A, 0x92, 0x93, 0x94, 0x95, 0x96, 0x97, 0x98, 0x99, 0x9A, 0xA2, 0xA3,
    0xA4, 0xA5, 0xA6, 0xA7, 0xA8, 0xA9, 0xAA, 0xB2, 0xB3, 0xB4, 0xB5, 0xB6,
    0xB7, 0xB8, 0xB9, 0xBA, 0xC2, 0xC3, 0xC4, 0xC5, 0xC6, 0xC7, 0xC8, 0xC9,
    0xCA, 0xD2, 0xD3, 0xD4, 0xD5, 0xD6, 0xD7, 0xD8, 0xD9, 0xDA, 0xE1, 0xE2,
    0xE3, 0xE4, 0xE5, 0xE6, 0xE7, 0xE8, 0xE9, 0xEA, 0xF1, 0xF2, 0xF3, 0xF4,
    0xF5, 0xF6, 0xF7, 0xF8, 0xF9, 0xFA)
SHARED_TABLES = {1: (K3_BITS, K3_VALS), 2: (K5_BITS, K5_VALS)}

TRANSFORM_CODES = {0: "exact", 1: "cordic", 2: "loeffler"}

# The paper's low-power CORDIC budget: 4 micro-rotations, 1/K as three
# signed powers of two, every stage output on a grid of 2**(12 - 8).
CORDIC_ITERATIONS = 4
CORDIC_GAIN_TERMS = 3
CORDIC_GRID = 2.0 ** (12 - 8)


class StreamError(ValueError):
    """The reference decoder rejects a stream."""


def qtable(quality: int) -> np.ndarray:
    """IJG quality scaling of the Annex K luminance table, (8, 8)."""
    q = int(min(max(quality, 1), 100))
    scale = 5000.0 / q if q < 50 else 200.0 - 2.0 * q
    return np.clip(np.floor((ANNEX_K_LUMA * scale + 50.0) / 100.0),
                   1.0, 255.0)


def dct_matrix() -> np.ndarray:
    """Orthonormal 8-point DCT-II matrix: X = C @ x."""
    k = np.arange(8)[:, None]
    i = np.arange(8)[None, :]
    c = np.cos(np.pi * k * (2 * i + 1) / 16.0) * math.sqrt(2.0 / 8.0)
    c[0] /= math.sqrt(2.0)
    return c


def to_blocks(img: np.ndarray) -> np.ndarray:
    """(H, W) with H, W multiples of 8 -> (H/8, W/8, 8, 8)."""
    h, w = img.shape
    return img.reshape(h // 8, 8, w // 8, 8).swapaxes(1, 2)


def from_blocks(blocks: np.ndarray) -> np.ndarray:
    gh, gw = blocks.shape[:2]
    return blocks.swapaxes(1, 2).reshape(gh * 8, gw * 8)


def pad8(img: np.ndarray) -> np.ndarray:
    h, w = img.shape
    return np.pad(img, ((0, (-h) % 8), (0, (-w) % 8)), mode="edge")


# ---------------------------------------------------------------------------
# Cordic-Loeffler 8-point graph (Loeffler 1989; Sun et al. 2006)
# ---------------------------------------------------------------------------

def _cordic_schedule(theta: float) -> tuple:
    """Greedy micro-rotation signs and the signed-power-of-two 1/K."""
    z, sigmas = theta, []
    for k in range(CORDIC_ITERATIONS):
        s = 1.0 if z >= 0 else -1.0
        z -= s * math.atan(2.0 ** -k)
        sigmas.append(s)
    gain = math.prod(math.sqrt(1.0 + 4.0 ** -k)
                     for k in range(CORDIC_ITERATIONS))
    target, approx = 1.0 / gain, 0.0
    for _ in range(CORDIC_GAIN_TERMS):
        resid = target - approx
        if resid == 0.0:
            break
        p = round(math.log2(abs(resid)))
        best = min((2.0 ** (p - 1), 2.0 ** p, 2.0 ** (p + 1)),
                   key=lambda c: abs(abs(resid) - c))
        approx += math.copysign(best, resid)
    return sigmas, approx


def _grid(t):
    return (t / CORDIC_GRID).round() * CORDIC_GRID


def _rotate(u, v, theta):
    """(u, v) -> (u cos + v sin, -u sin + v cos), by CORDIC on the grid."""
    sigmas, gain = _cordic_schedule(theta)
    for k, s in enumerate(sigmas):
        t = -s * 2.0 ** -k
        u, v = _grid(u - t * v), _grid(v + t * u)
    return _grid(u * gain), _grid(v * gain)


_A, _B, _E = 3 * math.pi / 16, math.pi / 16, math.pi / 8
_R = 1.0 / (2.0 * math.sqrt(2.0))


def _cordic_dct8(x):
    q = _grid
    a0, a1, a2, a3 = (q(x[0] + x[7]), q(x[1] + x[6]), q(x[2] + x[5]),
                      q(x[3] + x[4]))
    d3, d2, d1, d0 = (q(x[3] - x[4]), q(x[2] - x[5]), q(x[1] - x[6]),
                      q(x[0] - x[7]))
    b0, b1, b2, b3 = q(a0 + a3), q(a1 + a2), q(a1 - a2), q(a0 - a3)
    r4, r7 = _rotate(d3, d0, _A)
    r5, r6 = _rotate(d2, d1, _B)
    y0, y4 = q(b0 + b1), q(b0 - b1)
    c4, c5, c6, c7 = q(r4 + r6), q(r7 - r5), q(r4 - r6), q(r7 + r5)
    z2, z6 = _rotate(b3, b2, _E)
    return [q(y0 * _R), q((c4 + c7) * _R), q(z2 * 0.5), q(c5 * 0.5),
            q(y4 * _R), q(c6 * 0.5), q(-z6 * 0.5), q((c7 - c4) * _R)]


def _cordic_idct8(y):
    q = _grid
    y0, y4 = q(y[0] * _R), q(y[4] * _R)
    c4, c7 = q((y[1] - y[7]) * _R), q((y[1] + y[7]) * _R)
    c5, c6 = q(y[3] * 0.5), q(y[5] * 0.5)
    z2, z6 = q(y[2] * 0.5), q(-y[6] * 0.5)
    b0, b1 = q(y0 + y4), q(y0 - y4)
    b3, b2 = _rotate(z2, z6, -_E)
    r4, r6, r7, r5 = q(c4 + c6), q(c4 - c6), q(c7 + c5), q(c7 - c5)
    a0, a3, a1, a2 = q(b0 + b3), q(b0 - b3), q(b1 + b2), q(b1 - b2)
    d3, d0 = _rotate(r4, r7, -_A)
    d2, d1 = _rotate(r5, r6, -_B)
    return [q(a0 + d0), q(a1 + d1), q(a2 + d2), q(a3 + d3),
            q(a3 - d3), q(a2 - d2), q(a1 - d1), q(a0 - d0)]


def _graph_2d(fn, blocks, rows_first: bool, xp=np):
    """Apply an 8-point graph along both block axes of (..., 8, 8)."""
    def along(x, axis):
        x = xp.moveaxis(x, axis, 0)
        return xp.moveaxis(xp.stack(fn([x[i] for i in range(8)])), 0, axis)
    if rows_first:
        return along(along(blocks, -1), -2)
    return along(along(blocks, -2), -1)


# ---------------------------------------------------------------------------
# Transform, quantise, reconstruct
# ---------------------------------------------------------------------------

def forward(blocks, transform: str, xp=np, matmul=np.matmul):
    """(..., 8, 8) level-shifted pixels -> DCT coefficients.

    ``xp`` and ``matmul`` let the same arithmetic run elsewhere (the
    control runs it on the device in a lower precision)."""
    if transform == "exact":
        c = xp.asarray(dct_matrix(), blocks.dtype)
        return matmul(matmul(c, blocks), c.T)
    if transform == "cordic":
        return _graph_2d(_cordic_dct8, blocks, rows_first=True, xp=xp)
    raise ValueError(f"no reference for transform {transform!r}")


def inverse(coeffs, transform: str, xp=np, matmul=np.matmul):
    if transform == "exact":
        c = xp.asarray(dct_matrix(), coeffs.dtype)
        return matmul(matmul(c.T, coeffs), c)
    if transform == "cordic":
        return _graph_2d(_cordic_idct8, coeffs, rows_first=False, xp=xp)
    raise ValueError(f"no reference for transform {transform!r}")


def encode_levels(img: np.ndarray, quality: int,
                  transform: str = "exact") -> np.ndarray:
    """(H, W) uint8 -> (ceil(H/8), ceil(W/8), 8, 8) int64 levels."""
    x = to_blocks(pad8(np.asarray(img)).astype(np.float64) - 128.0)
    return np.round(forward(x, transform) / qtable(quality)).astype(np.int64)


def decode_pixels(levels: np.ndarray, quality: int, shape: tuple,
                  transform: str = "exact") -> np.ndarray:
    """Levels -> (H, W) uint8 reconstruction cropped to ``shape``."""
    x = inverse(levels.astype(np.float64) * qtable(quality), transform)
    rec = np.clip(np.round(from_blocks(x) + 128.0), 0, 255).astype(np.uint8)
    return rec[:shape[0], :shape[1]]


def psnr(orig: np.ndarray, rec: np.ndarray) -> float:
    o = np.asarray(orig, np.float64)
    mse = np.mean((o - np.asarray(rec, np.float64)) ** 2)
    return 20.0 * math.log10(o.max() / math.sqrt(max(mse, 1e-12)))


# ---------------------------------------------------------------------------
# DCTZ decoder (format: docs/bitstream.md of the system under test)
# ---------------------------------------------------------------------------

_HEADER = struct.Struct("<4sBBBBIIBBHII")
_ZIGZAG = np.array(sorted(range(64), key=lambda r: (
    r // 8 + r % 8, r // 8 if (r // 8 + r % 8) % 2 else r % 8)))


def _lut(bits, vals) -> tuple:
    """Peek-16 lookup: (symbol, code length) per 16-bit window."""
    sym = np.zeros(1 << 16, np.int64)
    length = np.zeros(1 << 16, np.int64)
    code, k = 0, 0
    for n, count in enumerate(bits, start=1):
        for _ in range(count):
            lo, hi = code << (16 - n), (code + 1) << (16 - n)
            if hi > 1 << 16:
                raise StreamError("Huffman table over-subscribed")
            sym[lo:hi], length[lo:hi] = vals[k], n
            code, k = code + 1, k + 1
        code <<= 1
    return sym, length


def _segment(data: bytes, off: int) -> tuple:
    if off + 16 > len(data):
        raise StreamError("truncated table segment")
    bits = tuple(data[off:off + 16])
    n = sum(bits)
    if off + 16 + n > len(data):
        raise StreamError("truncated table symbols")
    vals = tuple(data[off + 16:off + 16 + n])
    if len(set(vals)) != n:
        raise StreamError("repeated symbol in table")
    return (bits, vals), off + 16 + n


def parse_dctz(data: bytes) -> tuple:
    """One stream -> (header dict, (gh, gw, 8, 8) int64 levels)."""
    if len(data) < 28:
        raise StreamError("truncated header")
    (magic, version, flags, quality, tcode, h, w, dc_id, ac_id, reserved,
     nbytes, crc) = _HEADER.unpack_from(data)
    if magic != b"DCTZ" or version not in (1, 2) or flags or reserved:
        raise StreamError("bad magic, version or reserved field")
    if tcode not in TRANSFORM_CODES or not 1 <= quality <= 100 or not h * w:
        raise StreamError("bad transform, quality or shape")
    off, tables = 28, []
    for tid in (dc_id, ac_id):
        if tid == 0:
            table, off = _segment(data, off)
        elif version == 2 and tid in SHARED_TABLES:
            table = SHARED_TABLES[tid]
        else:
            raise StreamError(f"unknown table id {tid}")
        tables.append(table)
    if len(data) != off + nbytes:
        raise StreamError("stream length disagrees with payload_nbytes")
    if zlib.crc32(data[4:24] + data[28:]) & 0xFFFFFFFF != crc:
        raise StreamError("CRC mismatch")
    gh, gw = -(-h // 8), -(-w // 8)
    zz = _decode_payload(data[off:], gh * gw, *tables)
    blocks = np.zeros((gh * gw, 64), np.int64)
    blocks[:, _ZIGZAG] = zz
    hdr = {"quality": quality, "transform": TRANSFORM_CODES[tcode],
           "height": h, "width": w, "version": version}
    return hdr, blocks.reshape(gh, gw, 8, 8)


def _codes(bits, vals) -> dict:
    """Canonical code of each symbol: symbol -> (code, length)."""
    out, code, k = {}, 0, 0
    for n, count in enumerate(bits, start=1):
        for _ in range(count):
            out[vals[k]] = (code, n)
            code, k = code + 1, k + 1
        code <<= 1
    return out


def encode_dctz(levels: np.ndarray, quality: int, transform: str,
                shape: tuple) -> bytes:
    """(gh, gw, 8, 8) levels -> a version-2 DCTZ stream coded with the
    shared Annex K tables (ids 1 and 2)."""
    dc_codes, ac_codes = _codes(K3_BITS, K3_VALS), _codes(K5_BITS, K5_VALS)
    zz = np.asarray(levels).reshape(-1, 64)[:, _ZIGZAG].tolist()
    fields, pred = [], 0
    for row in zz:
        diff, pred = row[0] - pred, row[0]
        s = abs(diff).bit_length()
        fields.append(dc_codes[s])
        if s:
            fields.append((diff if diff > 0 else diff + (1 << s) - 1, s))
        last = max((k for k in range(1, 64) if row[k]), default=0)
        run = 0
        for k in range(1, last + 1):
            v = row[k]
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
    code = {v: k for k, v in TRANSFORM_CODES.items()}[transform]
    header = _HEADER.pack(b"DCTZ", 2, 0, quality, code, shape[0], shape[1],
                          1, 2, 0, len(payload), 0)
    crc = zlib.crc32(header[4:24] + payload) & 0xFFFFFFFF
    return header[:24] + struct.pack("<I", crc) + payload


def _amp(bits: int, size: int) -> int:
    return bits if bits >= 1 << (size - 1) else bits - (1 << size) + 1


def _decode_payload(payload: bytes, n_blocks: int, dc_table,
                    ac_table) -> np.ndarray:
    """JPEG baseline run-length payload -> (n_blocks, 64) zig-zag levels."""
    bits = np.unpackbits(np.frombuffer(payload, np.uint8))
    nbits = bits.size
    bits = np.concatenate([bits, np.ones(32, np.uint8)])
    weights = 1 << np.arange(15, -1, -1, dtype=np.int64)
    peek = np.lib.stride_tricks.sliding_window_view(
        bits, 16)[:nbits + 16].astype(np.int64) @ weights
    peek = peek.tolist()
    dc_sym, dc_len = (a.tolist() for a in _lut(*dc_table))
    ac_sym, ac_len = (a.tolist() for a in _lut(*ac_table))
    if max(dc_table[1], default=0) > 15:
        raise StreamError("DC table codes a symbol above 15")
    out = np.zeros((n_blocks, 64), np.int64)
    pos, pred = 0, 0
    for b in range(n_blocks):
        row = out[b]
        if pos >= nbits:
            raise StreamError("payload ends mid-stream")
        win = peek[pos]
        n = dc_len[win]
        if not n:
            raise StreamError(f"no DC code at bit {pos}")
        s = dc_sym[win]
        pos += n
        diff = _amp(peek[pos] >> (16 - s), s) if s else 0
        pos += s
        pred += diff
        row[0] = pred
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
            row[k] = _amp(peek[pos] >> (16 - size), size)
            pos += size
            k += 1
        if pos > nbits:
            raise StreamError("payload ends mid-block")
    return out
