"""Colour images on the engine's normal path, against the plain reference.

The engine codes (H, W, 3) RGB images as ``DCTZ`` version-3 YCbCr 4:2:0
streams. Here, at small sizes on the CPU, it is compared with the
benchmark's float64 reference (``perfbench/reference_colour.py``, which
imports nothing of the program): per-component levels, streams the
reference's independent decoder reads, and RGB within float32 round-off
— on every entropy backend (Pallas in interpret mode), on both sides of
the device symbolize guard. Grayscale streams keep their bytes: golden
digests recorded before colour existed.
"""

import hashlib
import pathlib
import struct
import sys
import zlib

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from helpers.routes import reassemble_routes  # noqa: E402
from perfbench import colour as bench_colour  # noqa: E402
from perfbench import reference_colour as rc  # noqa: E402
from repro import obs  # noqa: E402
from repro.core import colour, entropy, images  # noqa: E402
from repro.core.entropy import container, dense, rle  # noqa: E402
from repro.kernels import symbolize, unpack_bits  # noqa: E402
from repro.serve import codec_engine as eng  # noqa: E402

# odd sizes exercise the 16x16 MCU padding; then landscape and portrait
SIZES = [(37, 53), (50, 34), (48, 80), (80, 48)]
# float32 round-off, in levels and in 8-bit pixel values
LEVEL_TOL = 1e-4
PIXEL_TOL = 1e-3


def _img(shape, seed=0, gen="cablecar_like"):
    return bench_colour.colour_image(gen, *shape, seed=seed)


def _gap(x, n) -> float:
    return float(np.maximum(0.0, np.abs(x - n) - 0.5).max(initial=0.0))


def _check_against_reference(img, blob, rec, quality=75):
    h, w = img.shape[:2]
    hdr, levels = rc.parse_dctz3(blob)
    assert (hdr["height"], hdr["width"], hdr["quality"]) == (h, w, quality)
    want = rc.unrounded_levels(img, quality)
    for x, n in zip(want, levels):
        assert _gap(x, n) <= LEVEL_TOL
    rec = np.asarray(rec)
    assert rec.shape == (h, w, 3) and rec.dtype == np.uint8
    assert _gap(rc.unrounded_rgb(levels, quality)[:h, :w], rec) <= PIXEL_TOL
    return levels


@pytest.mark.parametrize("shape", SIZES)
def test_engine_matches_plain_reference(shape):
    img = _img(shape, seed=shape[0])
    blobs = eng.encode_batch([img], 75)
    recs = eng.decode_batch(blobs)
    levels = _check_against_reference(img, blobs[0], recs[0])
    # with the shared Annex K tables the reference's scalar coder writes
    # the same bytes from the same levels
    shared = eng.encode_batch([img], 75, tables="shared")[0]
    assert shared == rc.encode_dctz3(levels, 75, "exact", shape)


@pytest.mark.parametrize("guard", ["device", "host"])
@pytest.mark.parametrize("unpack", ["numpy", "pallas"])
@pytest.mark.parametrize("pack", ["numpy", "pallas"])
@pytest.mark.parametrize("sym", ["numpy", "pallas"])
def test_streams_identical_across_backends(monkeypatch, pallas_route, sym,
                                           pack, unpack, guard):
    imgs = [_img(s, seed=i) for i, s in enumerate(SIZES)]
    want = eng.encode_batch(imgs, 75)
    want_rec = eng.decode_batch(want)
    pallas_route(*[stage for stage, backend in (("symbolize", sym),
                                                 ("pack", pack),
                                                 ("unpack", unpack))
                   if backend == "pallas"])
    if guard == "host":
        # the largest test image (240 blocks) stays under the device
        # guard; shrink the guard so these streams take the host route
        monkeypatch.setattr(symbolize.ops, "MAX_DEVICE_BLOCKS", 100)
    before = obs.counts()
    got = eng.encode_batch(imgs, 75)
    assert got == want
    routes = {k: v - before.get(k, 0) for k, v in obs.counts().items()}
    if sym == "pallas":
        n_dev = sum(colour.mcu_grid(*s)[0] * colour.mcu_grid(*s)[1] * 6
                    <= (100 if guard == "host" else 2048) for s in SIZES)
        assert routes.get("entropy.symbolize.interpret", 0) == n_dev
    rec = eng.decode_batch(got)
    for a, b in zip(rec, want_rec):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if unpack == "pallas":
        assert obs.counts().get("entropy.unpack.interpret", 0) - \
            before.get("entropy.unpack.interpret", 0) == len(imgs)


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
@pytest.mark.parametrize("tile_bits", [None, 256])
def test_unpack_matches_lut_walk_across_tiles(backend, tile_bits):
    img = _img((48, 80), seed=9)
    blob = entropy.encode_image(img, 60)
    z0, _ = entropy.decode_zigzag_host(blob)

    def up(*a, **k):
        return unpack_bits.unpack_bits(*a, backend=backend,
                                       tile_bits=tile_bits, interpret=True,
                                       **k)
    z1, hdr = entropy.decode_zigzag_host(blob, unpacker=up)
    np.testing.assert_array_equal(z0, z1)
    assert hdr["components"] == 3


def test_scalar_oracles_agree_on_two_classes():
    img = _img((37, 53), seed=4)
    z = colour.compress(img, 75)
    dc_diff = container.colour_dc_diff(z[:, 0])
    classes = colour.TABLE_CLASSES
    prep = dense.prepare(dc_diff, z[:, 1:], classes=classes)
    assert prep.dc_freq.shape == (2, 256)
    # the per-class histograms of the scalar oracle's symbol stream
    is_dc, syms, _, _ = rle.symbolize_reference(dc_diff, z[:, 1:])
    cls = rle.block_classes(classes, len(dc_diff))[np.cumsum(is_dc) - 1]
    for freq, mask in ((prep.dc_freq, is_dc), (prep.ac_freq, ~is_dc)):
        want = np.bincount(cls[mask] * 256 + syms[mask],
                           minlength=512).reshape(2, 256)
        np.testing.assert_array_equal(freq, want)
    dcs = tuple(entropy.huffman.DEFAULT_TABLES.get(d) for d, _ in
                entropy.huffman.STANDARD_IDS)
    acs = tuple(entropy.huffman.DEFAULT_TABLES.get(a) for _, a in
                entropy.huffman.STANDARD_IDS)
    payload = prep.payload(dcs, acs)
    want = rle.decode_payload_reference(payload, len(dc_diff), dcs, acs,
                                        classes)
    got = rle.decode_payload(payload, len(dc_diff), dcs, acs,
                             classes=classes)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(want[0], dc_diff)
    np.testing.assert_array_equal(
        container.colour_dc_integrate(want[0]), z[:, 0])


def test_single_image_api_and_stacked_batch_agree_with_engine():
    imgs = np.stack([_img((48, 64), seed=s) for s in range(3)])
    stacked = eng.encode_batch(imgs, 75)
    assert stacked == [entropy.encode_image(im, 75) for im in imgs]
    for blob, rec in zip(stacked, eng.decode_batch(stacked)):
        np.testing.assert_array_equal(np.asarray(rec),
                                      np.asarray(entropy.decode_image(blob)))
    # a ragged batch over several MCU buckets, under every table policy
    rag = [_img(s, seed=i) for i, s in enumerate(SIZES)]
    for tables in ("auto", "embedded", "shared"):
        assert eng.encode_batch(rag, 75, tables=tables) == [
            entropy.encode_image(im, 75, tables=tables) for im in rag]


def test_colour_decode_crops_each_bucket_once():
    # 40x56 and 48x64 share the 3x4 MCU grid; two 80x48 photos are a
    # bucket of one size
    imgs = [_img((40, 56), seed=0), _img((80, 48), seed=1),
            _img((48, 64), seed=2), _img((80, 48), seed=3)]
    blobs = eng.encode_batch(imgs, 75)
    recs, routes = reassemble_routes(lambda: eng.decode_batch(blobs))
    assert routes == {"program": 1, "per_image": 1}
    for im, blob, rec in zip(imgs, blobs, recs):
        assert rec.shape == im.shape
        np.testing.assert_array_equal(
            np.asarray(rec), np.asarray(entropy.decode_image(blob)))


@pytest.mark.parametrize("shape, route", [((48, 64), "direct"),
                                          ((40, 56), "program")])
def test_stacked_colour_roundtrip_returns_its_group(shape, route):
    import jax.numpy as jnp
    imgs = np.stack([_img(shape, seed=s) for s in range(3)])
    (rec, psnr), routes = reassemble_routes(
        lambda: eng.roundtrip_batch(imgs, 75))
    assert routes == {route: 1}
    assert rec.shape == imgs.shape
    refs = [colour.decompress(colour.compress(im, 75), *shape, 75)
            for im in imgs]
    for r, ref in zip(rec, refs):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))
    np.testing.assert_array_equal(
        psnr, np.asarray(eng._psnr_vec(jnp.asarray(imgs), jnp.stack(refs))))


def test_mixed_grayscale_and_colour_batch():
    gray = np.asarray(images.lena_like(40, 56, seed=2))
    rgb = _img((40, 56), seed=2)
    before = obs.counts()
    blobs = eng.encode_batch([gray, rgb, gray], 50)
    assert [entropy.read_header(b)["version"] for b in blobs][1] == 3
    assert blobs[0] == blobs[2] == entropy.encode_image(gray, 50)
    recs = eng.decode_batch(blobs)
    assert [np.shape(r) for r in recs] == [(40, 56), (40, 56, 3), (40, 56)]
    after = obs.counts()
    for key in ("engine.images.colour.encoded",
                "engine.images.colour.decoded"):
        assert after.get(key, 0) - before.get(key, 0) == 1


def test_service_submits_colour():
    import asyncio

    from repro.serve.service import CodecService, ServiceConfig
    img = _img((48, 64), seed=6)

    async def go():
        async with CodecService(ServiceConfig(default_quality=75,
                                              max_batch=2,
                                              max_wait_s=0.01)) as svc:
            return await svc.submit(img)

    resp = asyncio.run(go())
    assert resp.payload == entropy.encode_image(img, 75)
    _check_against_reference(img, resp.payload,
                             entropy.decode_image(resp.payload))


def test_v3_header_fields():
    img = _img((37, 53), seed=1)
    for tables, ids in (("shared", ((1, 2), (3, 4))),
                        ("embedded", ((0, 0), (0, 0)))):
        blob = entropy.encode_image(img, 75, tables=tables)
        hdr = entropy.read_header(blob)
        assert hdr["version"] == container.VERSION_COLOUR
        assert (hdr["height"], hdr["width"], hdr["quality"]) == (37, 53, 75)
        assert hdr["components"] == 3 and hdr["table_ids"] == ids
        assert blob[28:40] == bytes((1, 0x22, 0, 0, 2, 0x11, 1, 1,
                                     3, 0x11, 1, 1))
        assert entropy.verify_crc(blob)
        assert entropy.stream_layout(blob) == (3, 3 * 4)


def _recrc(b: bytes) -> bytes:
    crc = zlib.crc32(b[4:24] + b[28:]) & 0xFFFFFFFF
    return b[:24] + struct.pack("<I", crc) + b[28:]


@pytest.mark.parametrize("mutate,match", [
    (lambda b: b[:40], "truncated header"),
    (lambda b: _recrc(b[:16] + bytes([1]) + b[17:]), "colour layout"),
    (lambda b: _recrc(b[:17] + bytes([1]) + b[18:]), "colour layout"),
    (lambda b: _recrc(b[:29] + bytes([0x21]) + b[30:]), "component"),
    (lambda b: _recrc(b[:30] + bytes([1]) + b[31:]), "component"),
    (lambda b: _recrc(b[:42] + bytes([9]) + b[43:]), "table id"),
    (lambda b: b[:len(b) - 8], "truncated payload"),
    (lambda b: b + b"x", "trailing"),
    (lambda b: b[:-4] + bytes([b[-4] ^ 0xFF]) + b[-3:], "CRC"),
    # the header's fields after the magic are CRC-protected
    (lambda b: b[:6] + bytes([b[6] ^ 1]) + b[7:], "CRC"),
])
def test_malformed_v3_streams_rejected(mutate, match):
    blob = entropy.encode_image(_img((37, 53), seed=1), 75)
    with pytest.raises(entropy.BitstreamError, match=match):
        entropy.decode_zigzag_host(mutate(blob))


def test_v3_stream_has_no_single_block_grid():
    blob = entropy.encode_image(_img((32, 32)), 75)
    with pytest.raises(ValueError, match="colour"):
        entropy.decode_qcoeffs(blob)


# sha256[:16] of fixed-seed grayscale streams, recorded before colour
# existed: versions 1 and 2 keep their exact bytes
GOLDEN = {
    ("lena64x72", 50, "exact", "auto"): "ed4ae3d2e5d18b71",
    ("lena64x72", 50, "exact", "embedded"): "3e20cadb6510a5b2",
    ("lena64x72", 50, "exact", "shared"): "ed4ae3d2e5d18b71",
    ("cablecar48x40", 30, "cordic", "auto"): "dc8782739afe7c60",
}
GOLDEN_ENGINE = ["b6023843ec2be1cc", "c058bfe098672b08", "9b9289f36e1d916f"]


def _digest(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()[:16]


def _gray(name):
    return {"lena64x72": np.asarray(images.lena_like(64, 72, seed=3)),
            "cablecar48x40": np.asarray(images.cablecar_like(48, 40,
                                                             seed=5))}[name]


@pytest.mark.parametrize("key", sorted(GOLDEN))
def test_grayscale_golden_digests(key):
    name, quality, transform, tables = key
    blob = entropy.encode_image(_gray(name), quality, transform,
                                tables=tables)
    assert _digest(blob) == GOLDEN[key]


def test_grayscale_engine_golden_digests():
    blobs = eng.encode_batch([_gray("lena64x72"), _gray("cablecar48x40"),
                              np.asarray(images.lena_like(33, 41, seed=7))],
                             75)
    assert [_digest(b) for b in blobs] == GOLDEN_ENGINE
