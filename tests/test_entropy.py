"""Entropy-coded bitstream stage: zig-zag properties, RLE/Huffman
round-trips (random + adversarial blocks), vectorized-vs-reference
identity (the wire-format lock for the fast path), golden ``.dctz``
fixtures from the PR 3 encoder, container framing errors, bit-exactness
against the quantised array path, and the engine's batch byte path."""

import pathlib
import re

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core import codec, images
from repro.core.entropy import (BitstreamError, decode_image, decode_qcoeffs,
                                decode_zigzag_host, encode_image,
                                encode_qcoeffs, encode_zigzag_host,
                                read_header, verify_crc)
from repro.core.entropy import bitio, dense, huffman, rle, scan

DATA_DIR = pathlib.Path(__file__).parent / "data"


def _symbolize(dc_diff, ac):
    """The host symbolizer's coding-order symbol stream."""
    return dense.dense_to_stream(dense.symbolize_dense(dc_diff, ac))


def _roundtrip_blocks(dc_diff, ac):
    """symbolize -> tables -> payload -> decode, for (n,)+(n,63) arrays.

    Also asserts, on every use, that the host symbolizer and LUT walk
    match the scalar references at all three levels: symbol stream,
    payload bytes, and decoded blocks."""
    is_dc, syms, amp_vals, amp_lens = _symbolize(dc_diff, ac)
    ref = rle.symbolize_reference(dc_diff, ac)
    for got, want in zip((is_dc, syms, amp_vals, amp_lens), ref):
        np.testing.assert_array_equal(got, want)
    dc_freq, ac_freq = rle.symbol_frequencies(is_dc, syms)
    dc_t, ac_t = huffman.build_table(dc_freq), huffman.build_table(ac_freq)
    payload = rle.encode_payload(is_dc, syms, amp_vals, amp_lens, dc_t, ac_t)
    assert dense.encode_payload_dense(dense.symbolize_dense(dc_diff, ac),
                                      dc_t, ac_t) == payload
    out = rle.decode_payload(payload, len(dc_diff), dc_t, ac_t)
    ref_out = rle.decode_payload_reference(payload, len(dc_diff), dc_t, ac_t)
    np.testing.assert_array_equal(out[0], ref_out[0])
    np.testing.assert_array_equal(out[1], ref_out[1])
    return out


class TestZigzag:
    def test_perm_is_permutation_and_involution_with_inverse(self):
        perm = scan.zigzag_perm()
        inv = scan.inverse_zigzag_perm()
        assert sorted(perm.tolist()) == list(range(64))
        np.testing.assert_array_equal(perm[inv], np.arange(64))
        np.testing.assert_array_equal(inv[perm], np.arange(64))

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_unscan_inverts_scan(self, seed):
        blocks = jnp.asarray(np.random.default_rng(seed).integers(
            -500, 500, (3, 8, 8), dtype=np.int32))
        z = scan.zigzag_scan(blocks)
        np.testing.assert_array_equal(np.asarray(scan.zigzag_unscan(z)),
                                      np.asarray(blocks))

    def test_dc_differential_integrates_back(self):
        z = jnp.asarray(np.random.default_rng(0).integers(
            -100, 100, (7, 64), dtype=np.int32))
        dc_diff, ac = scan.dc_differential(z)
        dc = scan.dc_integrate(dc_diff)
        np.testing.assert_array_equal(np.asarray(dc), np.asarray(z[:, 0]))
        back = scan.assemble_stream(dc, ac)
        np.testing.assert_array_equal(np.asarray(back), np.asarray(z))


class TestRLEHuffman:
    @given(st.integers(0, 2**31 - 1), st.integers(1, 8))
    @settings(max_examples=15, deadline=None)
    def test_roundtrip_random_blocks(self, seed, n):
        rng = np.random.default_rng(seed)
        # mostly-zero AC (the realistic case) plus dense noise blocks
        ac = rng.integers(-1000, 1000, (n, 63))
        ac[rng.random((n, 63)) < 0.7] = 0
        dc_diff = rng.integers(-2000, 2000, (n,))
        dec_dc, dec_ac = _roundtrip_blocks(dc_diff, ac)
        np.testing.assert_array_equal(dec_dc, dc_diff)
        np.testing.assert_array_equal(dec_ac, ac)

    @pytest.mark.parametrize("name,dc,acrow", [
        ("all_zero", [0, 0, 0], np.zeros((3, 63), int)),
        ("single_giant_ac_last",
         [5], np.eye(1, 63, 62, dtype=int) * 32767),
        ("single_giant_negative_ac",
         [-32768 + 1], np.eye(1, 63, 40, dtype=int) * -32767),
        ("max_run_zrl",                    # 62 zeros then one coefficient
         [1], np.eye(1, 63, 62, dtype=int) * 3),
        ("alternating_runs",
         [7], np.tile([0, 0, 0, 0, 0, 0, 0, 0, 0, 1], 7)[:63]
         .reshape(1, 63)),
        ("dense_max",                      # no zero anywhere, all max cat
         [100], np.full((1, 63), 255)),
    ])
    def test_adversarial_blocks(self, name, dc, acrow):
        ac = np.asarray(acrow, dtype=np.int64)
        dc_diff = np.asarray(dc, dtype=np.int64)
        dec_dc, dec_ac = _roundtrip_blocks(dc_diff, ac)
        np.testing.assert_array_equal(dec_dc, dc_diff, err_msg=name)
        np.testing.assert_array_equal(dec_ac, ac, err_msg=name)

    def test_amplitude_range_rejected(self):
        # the host symbolizer raises the scalar oracle's exact message
        for dc, ac in [(np.array([2**16]), np.zeros((1, 63), int)),
                       (np.array([0]),
                        np.full((1, 63), 40000, dtype=np.int64))]:
            with pytest.raises(rle.RangeError) as want:
                rle.symbolize_reference(dc, ac)
            with pytest.raises(rle.RangeError,
                               match=re.escape(str(want.value))):
                dense.symbolize_dense(dc, ac)

    def test_pack_bits_msb_first_and_one_padded(self):
        out = bitio.pack_bits(np.array([0b101, 0b1]),
                              np.array([3, 1]))
        assert out == bytes([0b10111111])
        reader = bitio.BitReader(out)
        assert reader.take(3) == 0b101 and reader.take(1) == 1

    def test_bitreader_truncation_raises(self):
        reader = bitio.BitReader(b"\xff")
        reader.take(8)
        with pytest.raises(bitio.TruncatedStream):
            reader.take(1)


class TestHuffman:
    def test_canonical_codes_are_prefix_free_and_ordered(self):
        t = huffman.build_table(np.array([0, 50, 30, 10, 5, 3, 2]))
        codes = t.code_lengths()
        strs = [format(c, f"0{l}b") for c, l in codes]
        for i, a in enumerate(strs):
            for b in strs[i + 1:]:
                assert not b.startswith(a) and not a.startswith(b)
        # more frequent symbols never get longer codes
        lens = dict(zip(t.symbols, (l for _, l in codes)))
        assert lens[1] <= lens[6]

    def test_single_symbol_table(self):
        t = huffman.build_table(np.eye(1, 256, 7).ravel())
        assert t.symbols == (7,) and t.code_lengths() == [(0, 1)]

    def test_length_limit_16(self):
        # fibonacci-ish frequencies force depth > 16 before limiting
        freqs = np.zeros(40)
        a, b = 1, 1
        for s in range(40):
            freqs[s] = a
            a, b = b, a + b
        t = huffman.build_table(freqs)
        assert max(l for _, l in t.code_lengths()) <= 16

    def test_segment_roundtrip_and_validation(self):
        t = huffman.build_table(np.array([5, 3, 2, 1]))
        seg = t.to_segment()
        t2, off = huffman.CanonicalTable.from_segment(seg)
        assert t2 == t and off == len(seg)
        with pytest.raises(huffman.InvalidTable):
            huffman.CanonicalTable.from_segment(seg[:10])
        with pytest.raises(huffman.InvalidTable):   # Kraft overfull
            huffman.CanonicalTable(counts=(4,) + (0,) * 15,
                                   symbols=(1, 2, 3, 4))


class TestContainer:
    def test_bit_exact_against_quantised_path(self):
        # the acceptance criterion: decode(encode(img, q)) reproduces the
        # quantised-roundtrip reconstruction bit-exactly, bench images
        # included (sizes cut down for test speed)
        for gen, (h, w) in ((images.lena_like, (96, 96)),
                            (images.lena_like, (96, 102)),   # non-8-divisible
                            (images.cablecar_like, (64, 48))):
            img = gen(h, w)
            for q in (10, 50, 90):
                c = codec.compress(img, q)
                blob = c.to_bytes()
                rec_bytes = np.asarray(decode_image(blob))
                rec_array = np.asarray(codec.decompress(c))
                np.testing.assert_array_equal(rec_bytes, rec_array)

    def test_qcoeffs_lossless_and_header_fields(self):
        img = images.cablecar_like(72, 80)
        c = codec.compress(img, 30, "cordic")
        blob = c.to_bytes()
        qc, hdr = decode_qcoeffs(blob)
        np.testing.assert_array_equal(np.asarray(qc), np.asarray(c.qcoeffs))
        assert hdr["quality"] == 30 and hdr["transform"] == "cordic"
        assert (hdr["height"], hdr["width"]) == (72, 80)
        assert read_header(blob) == hdr

    def test_measured_nbytes_and_ratio(self):
        img = images.lena_like(128, 128)
        c = codec.compress(img, 50)
        assert c.nbytes == len(c.to_bytes())
        assert c.compression_ratio() == 128 * 128 / c.nbytes
        assert c.nbytes < 128 * 128          # actually compresses

    def test_from_bytes_equals_original(self):
        img = images.lena_like(64, 64)
        c = codec.compress(img, 50)
        c2 = codec.CompressedImage.from_bytes(c.to_bytes())
        assert c2.quality == 50 and c2.orig_shape == (64, 64)
        assert c2.to_bytes() == c.to_bytes()   # re-encode is stable

    @pytest.mark.parametrize("mutate,match", [
        (lambda b: b[:10], "truncated header"),
        (lambda b: b"JUNK" + b[4:], "not a DCTZ"),
        (lambda b: b[:4] + bytes([99]) + b[5:], "version"),
        (lambda b: b[:7] + bytes([9]) + b[8:], "transform"),
        (lambda b: b[:16] + bytes([9]) + b[17:], "table id"),
        (lambda b: b[:len(b) - 8], "truncated payload"),
        (lambda b: b + b"x", "trailing"),
        (lambda b: b[:-4] + bytes([b[-4] ^ 0xFF]) + b[-3:], "CRC"),
        # header fields after the magic are CRC-protected too: a flipped
        # quality bit must not dequantise plausibly with the wrong table
        (lambda b: b[:6] + bytes([b[6] ^ 1]) + b[7:], "CRC"),
    ])
    def test_malformed_streams_rejected_with_clear_errors(self, mutate,
                                                          match):
        blob = encode_image(images.lena_like(40, 40), 50)
        with pytest.raises(BitstreamError, match=match):
            decode_qcoeffs(mutate(blob))

    def test_crafted_huge_shape_rejected_before_allocation(self):
        # a crafted header with a valid CRC but an absurd shape must be
        # rejected by the block-count bound, not die in np allocation
        import struct
        import zlib
        blob = bytearray(encode_image(images.lena_like(40, 40), 50))
        struct.pack_into("<II", blob, 8, 0xFFFFFF00, 0xFFFFFF00)
        crc = zlib.crc32(bytes(blob[4:24]) + bytes(blob[28:]))
        struct.pack_into("<I", blob, 24, crc & 0xFFFFFFFF)
        with pytest.raises(BitstreamError, match="cannot hold"):
            decode_qcoeffs(bytes(blob))

    def test_encode_validates_inputs(self):
        qc = np.zeros((2, 2, 8, 8), np.int32)
        with pytest.raises(ValueError, match="quality"):
            encode_qcoeffs(qc, 0, "exact", (16, 16))
        with pytest.raises(ValueError, match="transform"):
            encode_qcoeffs(qc, 50, "dst", (16, 16))
        with pytest.raises(ValueError, match="block grid"):
            encode_qcoeffs(qc, 50, "exact", (64, 64))

    def test_bpp_monotone_in_quality(self):
        img = images.lena_like(96, 96)
        sizes = [len(encode_image(img, q)) for q in (10, 50, 90)]
        assert sizes[0] < sizes[1] < sizes[2]


class TestVectorizedVsReference:
    """The fast path's contract: bit-for-bit identical to the scalar
    reference oracles on streams the reference can produce."""

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=15, deadline=None)
    def test_symbolize_matches_reference(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 24))
        ac = rng.integers(-32767, 32768, (n, 63))
        ac[rng.random((n, 63)) < rng.uniform(0.2, 0.995)] = 0
        dc_diff = rng.integers(-32767, 32768, (n,))
        got = _symbolize(dc_diff, ac)
        want = rle.symbolize_reference(dc_diff, ac)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_decode_truncation_matches_reference_semantics(self):
        dc = np.arange(-8, 8)
        ac = np.zeros((16, 63), np.int64)
        ac[:, ::7] = np.arange(1, 17)[:, None]
        is_dc, syms, av, al = _symbolize(dc, ac)
        dc_f, ac_f = rle.symbol_frequencies(is_dc, syms)
        dc_t, ac_t = huffman.build_table(dc_f), huffman.build_table(ac_f)
        payload = rle.encode_payload(is_dc, syms, av, al, dc_t, ac_t)
        for cut in (0, 1, len(payload) // 2, len(payload) - 1):
            with pytest.raises(ValueError):
                rle.decode_payload(payload[:cut], 16, dc_t, ac_t)
        # asking for more blocks than the stream holds must also raise
        with pytest.raises(ValueError):
            rle.decode_payload(payload, 17, dc_t, ac_t)

    def test_out_of_spec_dc_table_rejected(self):
        # a DC table coding symbol 16 passes CanonicalTable validation
        # (symbols are only bounded to bytes) but is out of spec for the
        # DC alphabet (categories are 0..15) — the decoder must reject
        # it rather than guess an amplitude width
        bad_dc = huffman.CanonicalTable(counts=(2,) + (0,) * 15,
                                        symbols=(0, 16))
        ac_t = huffman.build_table(np.eye(1, 256, rle.EOB).ravel())
        with pytest.raises(ValueError, match="DC table"):
            rle.decode_payload(b"\x00", 1, bad_dc, ac_t)

    def test_truncation_raises_truncated_stream_not_overrun(self):
        # padding bits after a truncation point can mimic a valid symbol
        # whose run would overrun the block; the decoder must report
        # truncation (any bit past the payload end), like the reference
        dc = np.zeros(4, np.int64)
        ac = np.zeros((4, 63), np.int64)
        ac[:, 60] = 3
        is_dc, syms, av, al = _symbolize(dc, ac)
        dc_f, ac_f = rle.symbol_frequencies(is_dc, syms)
        dc_t, ac_t = huffman.build_table(dc_f), huffman.build_table(ac_f)
        payload = rle.encode_payload(is_dc, syms, av, al, dc_t, ac_t)
        for cut in range(len(payload)):
            with pytest.raises(ValueError):
                rle.decode_payload(payload[:cut], 4, dc_t, ac_t)

    def test_bit_windows_matches_bitreader_peek16(self):
        payload = bytes([0b10110010, 0b01111000, 0xFF])
        win = bitio.bit_windows(payload)
        reader = bitio.BitReader(payload)
        for p in range(len(payload) * 8 + 1):
            reader.pos = p
            assert win[p] == reader.peek16(), f"bit {p}"

    def test_bench_identity_gate_is_clean(self):
        from repro.bench.cases import entropy_identity_violations
        assert entropy_identity_violations(trials=5) == []

    def test_packing_identity_gate_is_clean(self):
        # random + adversarial field streams AND whole framed streams:
        # staged NumPy reference and Pallas kernel == bitio.pack_bits
        from repro.bench.cases import packing_identity_violations
        assert packing_identity_violations(trials=5) == []


class TestGoldenFixtures:
    """Wire-format lock: v1 streams encoded at the PR 3 revision must be
    reproduced byte-for-byte (under ``tables="embedded"``, which pins
    the v1 layout) and still decode under the v2 reader; v2 fixtures
    lock the shared-table layout and the deterministic auto cost rule."""

    FIXTURES = [
        ("lena_40x40_q50_exact.dctz",
         lambda: images.lena_like(40, 40), 50, "exact"),
        ("lena_64x72_q90_exact.dctz",
         lambda: images.lena_like(64, 72, seed=2), 90, "exact"),
        ("cablecar_48x40_q30_cordic.dctz",
         lambda: images.cablecar_like(48, 40), 30, "cordic"),
        ("lena_33x41_q10_loeffler.dctz",
         lambda: images.lena_like(33, 41, seed=7), 10, "loeffler"),
    ]
    # (name, image_fn, quality, transform, (dc_id, ac_id)): encoded with
    # tables="auto" at the PR 5 revision; the second fixture locks the
    # per-alphabet choice (shared DC, embedded AC)
    FIXTURES_V2 = [
        ("lena_40x40_q50_exact_v2.dctz",
         lambda: images.lena_like(40, 40), 50, "exact", (1, 2)),
        ("lena_64x72_q90_exact_v2.dctz",
         lambda: images.lena_like(64, 72, seed=2), 90, "exact", (1, 0)),
    ]

    @pytest.mark.parametrize("name,image_fn,quality,transform", FIXTURES)
    def test_encoder_reproduces_golden_v1_stream(self, name, image_fn,
                                                 quality, transform):
        golden = (DATA_DIR / name).read_bytes()
        assert read_header(golden)["version"] == 1
        assert encode_image(image_fn(), quality, transform,
                            tables="embedded") == golden

    @pytest.mark.parametrize("name,image_fn,quality,transform", FIXTURES)
    def test_v2_reader_decodes_golden_v1_stream(self, name, image_fn,
                                                quality, transform):
        golden = (DATA_DIR / name).read_bytes()
        hdr = read_header(golden)
        assert hdr["quality"] == quality
        assert hdr["transform"] == transform
        img = image_fn()
        assert (hdr["height"], hdr["width"]) == img.shape
        rec = np.asarray(decode_image(golden))
        want = np.asarray(codec.decompress(codec.compress(
            img, quality, transform)))
        np.testing.assert_array_equal(rec, want)

    @pytest.mark.parametrize("name,image_fn,quality,transform,ids",
                             FIXTURES_V2)
    def test_encoder_reproduces_golden_v2_stream(self, name, image_fn,
                                                 quality, transform, ids):
        golden = (DATA_DIR / name).read_bytes()
        hdr = read_header(golden)
        assert hdr["version"] == 2
        assert (hdr["dc_table_id"], hdr["ac_table_id"]) == ids
        assert encode_image(image_fn(), quality, transform) == golden

    @pytest.mark.parametrize("name,image_fn,quality,transform,ids",
                             FIXTURES_V2)
    def test_decoder_reads_golden_v2_stream(self, name, image_fn,
                                            quality, transform, ids):
        golden = (DATA_DIR / name).read_bytes()
        rec = np.asarray(decode_image(golden))
        want = np.asarray(codec.decompress(codec.compress(
            image_fn(), quality, transform)))
        np.testing.assert_array_equal(rec, want)


class TestSharedTables:
    """Container v2: well-known shared Huffman tables by id, cost-based
    selection, and version negotiation against v1."""

    def test_registry_contents_are_canonical(self):
        assert huffman.DEFAULT_TABLES.ids() == (1, 2, 3, 4)
        for dc_id, ac_id in huffman.STANDARD_IDS:
            dc = huffman.DEFAULT_TABLES.get(dc_id)
            assert dc.symbols == tuple(range(12))
            ac = huffman.DEFAULT_TABLES.get(ac_id)
            assert len(ac.symbols) == 162
            assert rle.EOB in ac.symbols and rle.ZRL in ac.symbols

    def test_registry_validates(self):
        reg = huffman.TableRegistry()
        t = huffman.build_table(np.array([5, 3]))
        with pytest.raises(ValueError, match="1..255"):
            reg.register(0, t)
        reg.register(7, t)
        with pytest.raises(ValueError, match="already registered"):
            reg.register(7, t)
        assert reg.known(7) and not reg.known(8)
        with pytest.raises(KeyError):
            reg.get(8)

    def test_coded_bits_cost_model(self):
        t = huffman.build_table(np.array([5, 3, 2]))
        lens = dict(zip(t.symbols, (l for _, l in t.code_lengths())))
        freqs = np.zeros(256, np.int64)
        freqs[[0, 1, 2]] = [5, 3, 2]
        assert huffman.coded_bits(t, freqs) == (5 * lens[0] + 3 * lens[1]
                                                + 2 * lens[2])
        freqs[9] = 1                       # symbol the table cannot code
        assert huffman.coded_bits(t, freqs) is None

    @pytest.mark.parametrize("tables", ["shared", "auto", "embedded"])
    def test_roundtrip_bit_exact_under_every_policy(self, tables):
        img = images.lena_like(56, 48)
        blob = encode_image(img, 50, tables=tables)
        rec = np.asarray(decode_image(blob))
        want = np.asarray(codec.decompress(codec.compress(img, 50)))
        np.testing.assert_array_equal(rec, want)

    def test_version_negotiation_and_size_win(self):
        img = images.lena_like(40, 40)
        v1 = encode_image(img, 50, tables="embedded")
        v2 = encode_image(img, 50, tables="shared")
        assert read_header(v1)["version"] == 1
        h2 = read_header(v2)
        assert h2["version"] == 2
        assert (h2["dc_table_id"], h2["ac_table_id"]) == (
            huffman.STANDARD_DC_LUMA_ID, huffman.STANDARD_AC_LUMA_ID)
        # shared streams skip the ~56 embedded table bytes
        assert len(v2) < len(v1)

    def test_auto_never_larger_than_embedded(self):
        for q in (10, 50, 90):
            img = images.lena_like(48, 56, seed=q)
            assert len(encode_image(img, q)) <= len(
                encode_image(img, q, tables="embedded"))

    def test_shared_raises_when_uncoverable(self):
        # a 15-bit amplitude needs an AC size the Annex K table lacks
        z = np.zeros((1, 64), np.int64)
        z[0, 1] = 32767
        with pytest.raises(ValueError, match="shared table"):
            encode_zigzag_host(z, 50, "exact", (8, 8), tables="shared")

    def test_auto_falls_back_per_alphabet_on_uncoverable(self):
        z = np.zeros((1, 64), np.int64)
        z[0, 1] = 32767
        blob = encode_zigzag_host(z, 50, "exact", (8, 8))
        hdr = read_header(blob)
        # AC must embed (category 15 uncoverable); DC still goes shared
        assert hdr["ac_table_id"] == 0
        assert hdr["dc_table_id"] == huffman.STANDARD_DC_LUMA_ID
        zz, _ = decode_zigzag_host(blob)
        np.testing.assert_array_equal(zz, z)

    def test_v2_unknown_table_id_rejected(self):
        blob = bytearray(encode_image(images.lena_like(40, 40), 50,
                                      tables="shared"))
        blob[16] = 9                       # unregistered shared id
        with pytest.raises(BitstreamError, match="table id"):
            read_header(bytes(blob))

    def test_invalid_tables_mode_rejected(self):
        with pytest.raises(ValueError, match="tables mode"):
            encode_image(images.lena_like(8, 8), 50, tables="bogus")

    def test_verify_crc(self):
        for tables in ("embedded", "shared"):
            blob = encode_image(images.lena_like(40, 40), 50,
                                tables=tables)
            assert verify_crc(blob)
            assert not verify_crc(blob[:-1] + bytes([blob[-1] ^ 1]))
            assert not verify_crc(blob + b"x")
        with pytest.raises(BitstreamError):
            verify_crc(b"JUNKJUNK" * 8)


class TestHostHalves:
    """encode_zigzag_host / decode_zigzag_host — the jax-free halves the
    engine fans across threads — agree with the full-path
    container functions."""

    def test_encode_zigzag_host_matches_encode_qcoeffs(self):
        img = images.lena_like(72, 56)
        c = codec.compress(img, 40)
        z = np.asarray(scan.block_stream(jnp.asarray(c.qcoeffs)))
        blob_host = encode_zigzag_host(z, 40, "exact", (72, 56))
        assert blob_host == encode_qcoeffs(c.qcoeffs, 40, "exact", (72, 56))

    def test_decode_zigzag_host_matches_decode_qcoeffs(self):
        blob = encode_image(images.cablecar_like(48, 64), 60)
        z, hdr = decode_zigzag_host(blob)
        q, hdr2 = decode_qcoeffs(blob)
        assert hdr == hdr2
        np.testing.assert_array_equal(
            z, np.asarray(scan.block_stream(q)))

    def test_encode_zigzag_host_validates_inputs(self):
        z = np.zeros((4, 64), np.int32)
        with pytest.raises(ValueError, match="quality"):
            encode_zigzag_host(z, 0, "exact", (16, 16))
        with pytest.raises(ValueError, match="transform"):
            encode_zigzag_host(z, 50, "dst", (16, 16))
        with pytest.raises(ValueError, match="block grid"):
            encode_zigzag_host(z, 50, "exact", (64, 64))


class TestMemoisation:
    def test_build_table_memo_equals_build_table(self):
        freqs = np.zeros(256, np.int64)
        freqs[[0, 3, 7, 240]] = [50, 30, 10, 5]
        assert huffman.build_table_memo(freqs) == huffman.build_table(freqs)
        # cache hit returns the identical object
        assert huffman.build_table_memo(freqs) is huffman.build_table_memo(
            np.array(freqs))

    def test_decoder_luts_cached_per_table(self):
        t = huffman.build_table(np.array([5, 3, 2, 1]))
        sym1, len1 = huffman.decoder_luts(t)
        sym2, len2 = huffman.decoder_luts(
            huffman.CanonicalTable(t.counts, t.symbols))
        assert sym1 is sym2 and len1 is len2
        ref_sym, ref_len = t.decoder_lut()
        np.testing.assert_array_equal(sym1, ref_sym)
        np.testing.assert_array_equal(len1, ref_len)


class TestEngineBytePath:
    def test_stacked_and_ragged_match_single_image_bytes(self):
        from repro.serve import codec_engine
        stacked = np.stack([images.lena_like(64, 64, seed=i)
                            for i in range(3)])
        blobs = codec_engine.encode_batch(stacked, 50)
        assert blobs == [codec.compress(stacked[i], 50).to_bytes()
                         for i in range(3)]
        rag = [images.lena_like(64, 72), images.cablecar_like(40, 40)]
        blobs = codec_engine.encode_batch(rag, 70)
        assert blobs == [codec.compress(im, 70).to_bytes() for im in rag]

    def test_pipelined_and_serial_encode_bytes_identical(self):
        # the engine's one encode path (device zig-zag, then the thread
        # pool) writes the single-image encoders' bytes across a ragged
        # batch of several shape buckets, under every table policy
        from repro.serve import codec_engine
        rag = [images.lena_like(64, 72), images.cablecar_like(40, 40),
               images.lena_like(100, 90, seed=3),
               images.cablecar_like(64, 72, seed=4)]
        for tables in ("auto", "embedded", "shared"):
            got = codec_engine.encode_batch(rag, 50, tables=tables)
            assert got == [codec.compress(im, 50).to_bytes(tables=tables)
                           for im in rag]
            assert got == [encode_image(np.asarray(im), 50, tables=tables)
                           for im in rag]

    def test_decode_batch_bit_exact_mixed_streams(self):
        from repro.serve import codec_engine
        blobs = [encode_image(images.lena_like(64, 72), 50),
                 encode_image(images.cablecar_like(40, 40), 30),
                 encode_image(images.lena_like(64, 72, seed=2), 50)]
        # the pool decodes a batch; a single stream decodes serially
        pooled = codec_engine.decode_batch(blobs)
        single = [codec_engine.decode_batch([b])[0] for b in blobs]
        for blob, rec, one in zip(blobs, pooled, single):
            np.testing.assert_array_equal(
                np.asarray(rec), np.asarray(decode_image(blob)))
            np.testing.assert_array_equal(np.asarray(one), np.asarray(rec))
        with pytest.raises(ValueError):
            codec_engine.decode_batch([])

    def test_pack_backend_routing_is_byte_identical(self, pallas_route):
        from repro.serve import codec_engine
        rag = [images.lena_like(64, 72), images.cablecar_like(40, 40)]
        default = codec_engine.encode_batch(rag, 50)
        # the Pallas packing route (interpret mode off-TPU) must frame
        # identical streams through the whole engine path
        pallas_route("pack")
        cb = codec_engine.compress_batch(rag, 50)
        before = obs.counts().get("entropy.pack.interpret", 0)
        assert cb.to_bytes_list() == default
        assert obs.counts()["entropy.pack.interpret"] - before == 2

    def test_tables_policy_re_keys_the_stream_cache(self):
        from repro.serve import codec_engine
        rag = [images.lena_like(64, 72), images.cablecar_like(40, 40)]
        cb = codec_engine.compress_batch(rag, 50)
        auto = cb.to_bytes_list()
        emb = cb.to_bytes_list(tables="embedded")
        assert emb == [codec.compress(im, 50).to_bytes(tables="embedded")
                       for im in rag]
        assert emb != auto                  # policy changes the bytes
        assert cb.to_bytes_list() == auto   # and the cache re-keys

    def test_unpack_backend_routing_is_bit_identical(self, pallas_route):
        from repro.serve import codec_engine
        blobs = [encode_image(images.lena_like(48, 56, seed=i), 50)
                 for i in range(3)]
        default = codec_engine.decode_batch(blobs)
        # the Pallas decode route (interpret mode off-TPU) must
        # reconstruct identical images through the whole engine path,
        # on the pool and on the single-stream path
        pallas_route("unpack")
        before = obs.counts().get("entropy.unpack.interpret", 0)
        routed = codec_engine.decode_batch(blobs)
        serial = [codec_engine.decode_batch([b])[0] for b in blobs]
        assert obs.counts()["entropy.unpack.interpret"] - before == 6
        for a, b, c in zip(default, routed, serial):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
            np.testing.assert_array_equal(np.asarray(a), np.asarray(c))

    def test_process_pool_decodes_runtime_registered_tables(self):
        # a v2 stream referencing a table id registered at runtime must
        # decode on decode_batch's worker threads as on the caller's
        import struct
        import zlib

        from repro.core.entropy import container
        from repro.serve import codec_engine
        for tid, table in ((201, huffman.STANDARD_DC_LUMA),
                           (202, huffman.STANDARD_AC_LUMA)):
            if not huffman.DEFAULT_TABLES.known(tid):
                huffman.DEFAULT_TABLES.register(tid, table)
        img = np.asarray(images.lena_like(40, 40))
        z, _ = decode_zigzag_host(encode_image(img, quality=50))
        dc_diff = np.diff(z[:, 0].astype(np.int64), prepend=0)
        syms = _symbolize(dc_diff, z[:, 1:].astype(np.int64))
        payload = rle.encode_payload(*syms, huffman.STANDARD_DC_LUMA,
                                     huffman.STANDARD_AC_LUMA)
        h, w = img.shape
        header = container._HEADER.pack(container.MAGIC, 2, 0, 50, 0,
                                        h, w, 201, 202, 0, len(payload), 0)
        crc = zlib.crc32(header[4:24] + payload) & 0xFFFFFFFF
        blob = header[:24] + struct.pack("<I", crc) + payload
        want = np.asarray(decode_image(blob))
        out = codec_engine.decode_batch([blob, blob])
        for rec in out:
            np.testing.assert_array_equal(np.asarray(rec), want)

    def test_nbytes_estimate_measured_after_materialise(self):
        from repro.core import quant
        from repro.serve import codec_engine
        rag = [images.lena_like(64, 72), images.cablecar_like(40, 40)]
        cb = codec_engine.compress_batch(rag, 50)
        proxy = cb.nbytes_estimate()
        want_proxy = sum(float(quant.estimate_bits(g.qcoeffs)) / 8.0
                         for g in cb.groups)
        assert proxy == want_proxy
        streams = cb.to_bytes_list()
        measured = cb.nbytes_estimate()
        assert measured == float(sum(len(s) for s in streams))
        assert measured != proxy            # the proxy is only a model
        # repeated calls reuse the cached streams
        assert cb.to_bytes_list() == streams


class TestBigPayloadDecodeRouting:
    """PR 7 regression: `decode_payload` without an unpacker must not
    build linear-memory walk tables for huge payloads — above
    `_ROUTED_DECODE_MIN_BITS` it routes to the staged decoder
    (`repro.kernels.unpack_bits`), whose scratch is bounded per tile."""

    @staticmethod
    def _stream(n_blocks, seed=0, density=0.5, amplitude=512):
        rng = np.random.default_rng(seed)
        dc = rng.integers(-1024, 1025, (n_blocks,))
        ac = rng.integers(-amplitude, amplitude + 1, (n_blocks, 63))
        ac[rng.random((n_blocks, 63)) > density] = 0
        is_dc, syms, av, al = _symbolize(dc, ac)
        dc_f, ac_f = rle.symbol_frequencies(is_dc, syms)
        dc_t, ac_t = huffman.build_table(dc_f), huffman.build_table(ac_f)
        payload = rle.encode_payload(is_dc, syms, av, al, dc_t, ac_t)
        return payload, dc, ac, dc_t, ac_t

    def test_walk_tables_grow_linearly_but_staged_scratch_saturates(self):
        from repro.kernels import unpack_bits
        # the latent blowup: walk memory is ~16 B/bit with no ceiling,
        # while the staged decoder's scratch stops growing once one
        # tile's worth of positions is resident
        assert rle.walk_table_nbytes(1 << 24) > \
            7 * rle.walk_table_nbytes(1 << 21)
        assert unpack_bits.scratch_nbytes(1 << 21) == \
            unpack_bits.scratch_nbytes(1 << 24)
        # at the routing threshold the walk already costs more than the
        # staged decoder's (saturated) scratch ever will
        thr = rle._ROUTED_DECODE_MIN_BITS
        assert rle.walk_table_nbytes(thr + 8) > \
            unpack_bits.scratch_nbytes(thr + 8)
        # and the gap is what routing saves: linear vs constant
        assert rle.walk_table_nbytes(1 << 27) > \
            100 * unpack_bits.scratch_nbytes(1 << 27)

    def test_small_payloads_keep_the_walk(self, monkeypatch):
        payload, dc, ac, dc_t, ac_t = self._stream(8)
        monkeypatch.setattr(
            rle, "_staged_unpacker",
            lambda: (_ for _ in ()).throw(
                AssertionError("small payload must not route")))
        got_dc, got_ac = rle.decode_payload(payload, 8, dc_t, ac_t)
        np.testing.assert_array_equal(got_dc, dc)
        np.testing.assert_array_equal(got_ac, ac)

    def test_big_payloads_route_to_staged_decoder(self, monkeypatch):
        # shrink the threshold so routing triggers on a cheap stream,
        # and poison the walk-table builder: decode succeeding proves
        # the staged decoder served the request end to end
        payload, dc, ac, dc_t, ac_t = self._stream(32, seed=1)
        assert len(payload) * 8 > 256
        monkeypatch.setattr(rle, "_ROUTED_DECODE_MIN_BITS", 256)
        monkeypatch.setattr(
            rle, "_decode_table",
            lambda *a, **k: (_ for _ in ()).throw(
                AssertionError("big payload built walk tables")))
        got_dc, got_ac = rle.decode_payload(payload, 32, dc_t, ac_t)
        np.testing.assert_array_equal(got_dc, dc)
        np.testing.assert_array_equal(got_ac, ac)

    def test_missing_kernels_layer_falls_back_to_walk(self, monkeypatch):
        payload, dc, ac, dc_t, ac_t = self._stream(32, seed=2)
        monkeypatch.setattr(rle, "_ROUTED_DECODE_MIN_BITS", 256)
        monkeypatch.setattr(rle, "_staged_unpacker", lambda: None)
        got_dc, got_ac = rle.decode_payload(payload, 32, dc_t, ac_t)
        np.testing.assert_array_equal(got_dc, dc)
        np.testing.assert_array_equal(got_ac, ac)

    def test_above_threshold_payload_end_to_end(self):
        # a real > 2^20-bit payload: the default decode routes to the
        # staged decoder and still matches the scalar reference oracle
        n_blocks = 1400
        payload, dc, ac, dc_t, ac_t = self._stream(n_blocks, seed=3,
                                                   density=0.9,
                                                   amplitude=32767)
        assert len(payload) * 8 > rle._ROUTED_DECODE_MIN_BITS
        got_dc, got_ac = rle.decode_payload(payload, n_blocks, dc_t, ac_t)
        want_dc, want_ac = rle.decode_payload_reference(
            payload, n_blocks, dc_t, ac_t)
        np.testing.assert_array_equal(got_dc, want_dc)
        np.testing.assert_array_equal(got_ac, want_ac)

    def test_container_default_path_reaches_routing(self, monkeypatch):
        # decode_image with no unpacker (the latent-blowup entry point)
        # must inherit the routing fix
        from repro.core.entropy import container
        calls = []
        real = rle._staged_unpacker

        def spy():
            calls.append(True)
            return real()
        monkeypatch.setattr(rle, "_ROUTED_DECODE_MIN_BITS", 64)
        monkeypatch.setattr(rle, "_staged_unpacker", spy)
        img = images.lena_like(48, 48)
        blob = container.encode_image(np.asarray(img), quality=50)
        out = container.decode_image(blob)
        assert out.shape == (48, 48)
        assert calls, "decode_image default path bypassed the routing"
