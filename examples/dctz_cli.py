"""Compress a file to disk: encode/decode ``.dctz`` streams from the CLI.

The on-disk artifact is the real entropy-coded container
(``repro.core.entropy``, spec in docs/bitstream.md) — measured bytes,
not an in-memory coefficient array.  Grayscale images travel as binary
PGM (P5) or ``.npy``; ``demo:NAME:HxW`` synthesises the repo's Lena /
Cable-car stand-ins so the example runs with no input files at all.

    PYTHONPATH=src python examples/dctz_cli.py encode demo:lena:512x512 \
        /tmp/lena.dctz --quality 50
    PYTHONPATH=src python examples/dctz_cli.py info   /tmp/lena.dctz
    PYTHONPATH=src python examples/dctz_cli.py decode /tmp/lena.dctz \
        /tmp/lena_rec.pgm --verify-crc

``info`` and ``decode`` exit nonzero with a one-line ``error:``
diagnostic on a malformed stream (truncation, trailing bytes, CRC
mismatch, bad tables) instead of a traceback, so shell pipelines can
gate on corruption; ``decode --verify-crc`` checks the container CRC
explicitly before parsing and names the stored vs computed digests on
mismatch.
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

import numpy as np

from repro import compile_cache
from repro.core import entropy, images, metrics


def _timed(fn, *args):
    """(result, wall seconds) with one untimed warmup call (absorbs jit
    compilation so --time reports the steady-state the benches see)."""
    fn(*args)
    t0 = time.perf_counter()
    out = fn(*args)
    return out, time.perf_counter() - t0


def read_gray(spec: str) -> np.ndarray:
    """Load (H, W) uint8 from a .pgm/.npy path or a demo:NAME:HxW spec."""
    if spec.startswith("demo:"):
        _, name, size = spec.split(":")
        h, w = (int(s) for s in size.split("x"))
        fn = {"lena": images.lena_like,
              "cablecar": images.cablecar_like}[name]
        return fn(h, w)
    path = pathlib.Path(spec)
    if path.suffix == ".npy":
        arr = np.load(path)
        if arr.ndim != 2:
            raise SystemExit(f"{path}: expected a 2-D grayscale array, "
                             f"got shape {arr.shape}")
        return arr.astype(np.uint8)
    return _read_pgm(path)


def _read_pgm(path: pathlib.Path) -> np.ndarray:
    data = path.read_bytes()
    fields, pos = [], 0
    while len(fields) < 4:                     # magic, W, H, maxval
        end = min(i for i in (data.find(b" ", pos), data.find(b"\n", pos),
                              data.find(b"\t", pos)) if i != -1)
        tok = data[pos:end]
        if tok.startswith(b"#"):               # comment to end of line
            end = data.find(b"\n", pos)
        elif tok:
            fields.append(tok)
        pos = end + 1
    if fields[0] != b"P5":
        raise SystemExit(f"{path}: only binary PGM (P5) is supported")
    w, h, maxval = (int(f) for f in fields[1:])
    if maxval != 255:
        raise SystemExit(f"{path}: only 8-bit PGM supported")
    return np.frombuffer(data[pos:pos + h * w],
                         np.uint8).reshape(h, w).copy()


def write_gray(path: pathlib.Path, img: np.ndarray) -> None:
    """Write (H, W) uint8 as .npy or binary PGM, by extension."""
    if path.suffix == ".npy":
        np.save(path, img)
        return
    h, w = img.shape
    path.write_bytes(b"P5\n%d %d\n255\n" % (w, h)
                     + np.asarray(img, np.uint8).tobytes())


def cmd_encode(args) -> int:
    img = read_gray(args.input)
    h, w = img.shape
    enc = lambda: entropy.encode_image(img, args.quality, args.transform,
                                       tables=args.tables)
    if args.time:
        blob, dt = _timed(enc)
        print(f"encode: {dt * 1e3:.2f} ms "
              f"({h * w / 1e6 / dt:.1f} MB/s of pixels, "
              f"{1 / dt:.1f} img/s)")
    else:
        blob = enc()
    pathlib.Path(args.output).write_bytes(blob)
    bpp = len(blob) * 8 / (h * w)
    print(f"{args.output}: {len(blob)} bytes for {h}x{w} "
          f"({bpp:.3f} bits/px, {8 / bpp:.1f}x vs 8-bit raw)")
    return 0


def _stream_error(path: str, exc: Exception) -> int:
    """One-line diagnostic on stderr for a malformed stream, exit 1."""
    kind = ("truncated stream" if isinstance(exc, entropy.TruncatedStream)
            else "bad stream")
    print(f"error: {path}: {kind}: {exc}", file=sys.stderr)
    return 1


def cmd_decode(args) -> int:
    blob = pathlib.Path(args.input).read_bytes()
    if args.verify_crc:
        try:
            hdr = entropy.read_header(blob)
            if not entropy.verify_crc(blob):
                return _stream_error(
                    args.input, entropy.BitstreamError(
                        f"CRC mismatch (header says "
                        f"{hdr['crc32']:#010x})"))
        except (entropy.BitstreamError, entropy.TruncatedStream) as exc:
            return _stream_error(args.input, exc)
        print(f"{args.input}: crc ok")
    try:
        if args.time:
            rec, dt = _timed(entropy.decode_image, blob, args.mode)
            rec = np.asarray(rec)
            h, w = rec.shape
            print(f"decode: {dt * 1e3:.2f} ms "
                  f"({h * w / 1e6 / dt:.1f} MB/s of pixels, "
                  f"{1 / dt:.1f} img/s)")
        else:
            rec = np.asarray(entropy.decode_image(blob, mode=args.mode))
    except (entropy.BitstreamError, entropy.TruncatedStream) as exc:
        return _stream_error(args.input, exc)
    write_gray(pathlib.Path(args.output), rec)
    print(f"{args.output}: {rec.shape[0]}x{rec.shape[1]} reconstructed")
    if args.original:
        orig = read_gray(args.original)
        print(f"PSNR vs {args.original}: "
              f"{float(metrics.psnr(orig, rec)):.2f} dB")
    return 0


def _table_desc(table_id: int) -> str:
    """Human name for a container table id (0 embeds, >= 1 is shared)."""
    return "embedded" if table_id == 0 else f"shared#{table_id}"


def cmd_info(args) -> int:
    data = pathlib.Path(args.input).read_bytes()
    try:
        hdr = entropy.read_header(data)
        crc_ok = entropy.verify_crc(data)
    except (entropy.BitstreamError, entropy.TruncatedStream) as exc:
        return _stream_error(args.input, exc)
    px = hdr["height"] * hdr["width"]
    pairs = hdr.get("table_ids",
                    ((hdr.get("dc_table_id"), hdr.get("ac_table_id")),))
    tables = " ".join(f"(dc:{_table_desc(dc)},ac:{_table_desc(ac)})"
                      for dc, ac in pairs)
    print(f"{args.input}: DCTZ v{hdr['version']} "
          f"{hdr['height']}x{hdr['width']} quality={hdr['quality']} "
          f"transform={hdr['transform']} "
          f"{'colour ' if len(pairs) > 1 else ''}tables={tables} "
          f"crc={'ok' if crc_ok else 'MISMATCH'} "
          f"payload={hdr['payload_nbytes']}B "
          f"total={len(data)}B ({len(data) * 8 / px:.3f} bits/px)")
    if not crc_ok:
        return _stream_error(args.input, entropy.BitstreamError(
            f"CRC mismatch (header says {hdr['crc32']:#010x})"))
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    enc = sub.add_parser("encode", help="image file -> .dctz")
    enc.add_argument("input", help=".pgm/.npy path or demo:NAME:HxW")
    enc.add_argument("output", help=".dctz output path")
    enc.add_argument("--quality", type=int, default=50)
    enc.add_argument("--transform", default="exact",
                     choices=["exact", "cordic", "loeffler"])
    enc.add_argument("--tables", default="auto",
                     choices=["auto", "embedded", "shared"],
                     help="Huffman table policy: auto picks shared "
                          "well-known tables (container v2) when they "
                          "beat the embedded-table cost; embedded "
                          "forces the v1 layout")
    enc.add_argument("--time", action="store_true",
                     help="print encode wall time and MB/s (one warmup "
                          "call first, so jit compilation is excluded)")
    enc.set_defaults(fn=cmd_encode)

    dec = sub.add_parser("decode", help=".dctz -> image file")
    dec.add_argument("input", help=".dctz path")
    dec.add_argument("output", help=".pgm/.npy output path")
    dec.add_argument("--mode", default="standard",
                     choices=["standard", "matched"])
    dec.add_argument("--original", default=None,
                     help="optional original image to PSNR against")
    dec.add_argument("--verify-crc", action="store_true",
                     help="check the container CRC before parsing and "
                          "fail with the stored vs computed digests on "
                          "mismatch")
    dec.add_argument("--time", action="store_true",
                     help="print decode wall time and MB/s (one warmup "
                          "call first, so jit compilation is excluded)")
    dec.set_defaults(fn=cmd_decode)

    info = sub.add_parser("info", help="print a .dctz header")
    info.add_argument("input", help=".dctz path")
    info.set_defaults(fn=cmd_info)

    args = ap.parse_args()
    return args.fn(args)


if __name__ == "__main__":
    compile_cache.enable()
    sys.exit(main())
