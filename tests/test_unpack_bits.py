"""Routed entropy unpack: the staged NumPy reference and the Pallas
speculative-decode kernel must be coefficient-identical to the scalar
``decode_payload_reference`` oracle on every stream — including the
errors malformed streams raise — mirroring ``pack_bits``' suite on the
encode side."""

import pathlib

import numpy as np
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import obs
from repro.core.entropy import bitio, container, dense, huffman, rle
from repro.kernels import unpack_bits
from repro.kernels.unpack_bits import ref as unpack_ref

DATA_DIR = pathlib.Path(__file__).parent / "data"


def _encode(dc_diff, ac, std_tables=True):
    """Blocks -> (payload, dc_table, ac_table)."""
    syms = dense.dense_to_stream(dense.symbolize_dense(
        np.asarray(dc_diff, np.int64), np.asarray(ac, np.int64)))
    if std_tables:
        dc_t, ac_t = huffman.STANDARD_DC_LUMA, huffman.STANDARD_AC_LUMA
    else:
        dc_f, ac_f = rle.symbol_frequencies(syms[0], syms[1])
        dc_t, ac_t = huffman.build_table(dc_f), huffman.build_table(ac_f)
    return rle.encode_payload(*syms, dc_t, ac_t), dc_t, ac_t


def _random_blocks(rng, n, hi=1000):
    dc = rng.integers(-hi, hi + 1, n)
    ac = np.zeros((n, 63), np.int64)
    for b in range(n):
        k = int(rng.integers(0, 16))
        cols = rng.choice(63, size=k, replace=False)
        ac[b, cols] = rng.integers(-hi, hi + 1, k)
    return dc, ac


class TestUnpackBitsKernel:
    @staticmethod
    def _all(payload, n_blocks, dc_t, ac_t, tile_sizes=(64,)):
        """Every backend must match the scalar oracle exactly."""
        want = rle.decode_payload_reference(payload, n_blocks, dc_t, ac_t)
        outs = [unpack_ref.unpack_bits_ref(payload, n_blocks, dc_t, ac_t)]
        outs += [unpack_ref.unpack_bits_ref(payload, n_blocks, dc_t, ac_t,
                                            tile_bits=tb)
                 for tb in tile_sizes]
        outs.append(unpack_bits.unpack_bits(payload, n_blocks, dc_t, ac_t,
                                            backend="pallas",
                                            interpret=True))
        for dc, ac in outs:
            np.testing.assert_array_equal(dc, want[0])
            np.testing.assert_array_equal(ac, want[1])
        return want

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=10, deadline=None)
    def test_random_streams(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 150))
        dc, ac = _random_blocks(rng, n)
        payload, dc_t, ac_t = _encode(dc, ac, std_tables=bool(n % 2))
        self._all(payload, n, dc_t, ac_t)

    def test_empty_and_trivial_blocks(self):
        # zero blocks: empty output, no stream validation (reference
        # semantics), on every backend
        dc_t, ac_t = huffman.STANDARD_DC_LUMA, huffman.STANDARD_AC_LUMA
        for fn in (unpack_ref.unpack_bits_ref,
                   lambda *a: unpack_bits.unpack_bits(
                       *a, backend="pallas", interpret=True)):
            dc, ac = fn(b"\xAB\xCD", 0, dc_t, ac_t)
            assert dc.shape == (0,) and ac.shape == (0, 63)
        # all-zero blocks: DC category 0 + EOB only
        payload, dc_t, ac_t = _encode(np.zeros(9), np.zeros((9, 63)))
        self._all(payload, 9, dc_t, ac_t, tile_sizes=(1, 7))

    def test_all_zrl_chains(self):
        # a lone coefficient at column 62 costs three ZRLs + a run-14
        # symbol; stacking such blocks makes ZRL the dominant unit and
        # exercises the doubling's 16-position hops
        n = 40
        ac = np.zeros((n, 63), np.int64)
        ac[:, 62] = 7
        payload, dc_t, ac_t = _encode(np.zeros(n), ac, std_tables=False)
        self._all(payload, n, dc_t, ac_t, tile_sizes=(33, 64))

    def test_max_category_amplitudes(self):
        # +/-32767 needs category 15 — the widest legal amplitude field
        # (code + 15 bits) and the largest unit advance
        n = 12
        rng = np.random.default_rng(3)
        dc = rng.choice([-32767, 32767], n)
        ac = np.zeros((n, 63), np.int64)
        ac[:, rng.choice(63, 8, replace=False)] = 32767
        ac[:, 0] = -32767
        payload, dc_t, ac_t = _encode(dc, ac, std_tables=False)
        self._all(payload, n, dc_t, ac_t)

    def test_dense_blocks(self):
        # every AC slot nonzero: 64 units per block, the doubling's
        # worst case (chains must terminate by crossing, never EOB)
        n = 6
        rng = np.random.default_rng(4)
        ac = rng.integers(1, 500, (n, 63))
        payload, dc_t, ac_t = _encode(rng.integers(-500, 500, n), ac)
        self._all(payload, n, dc_t, ac_t)

    def test_tile_boundary_straddles(self):
        # blocks whose codewords straddle resolver tile boundaries in
        # every phase: tiny tiles shift the boundary through the chain
        rng = np.random.default_rng(5)
        dc, ac = _random_blocks(rng, 50)
        payload, dc_t, ac_t = _encode(dc, ac)
        self._all(payload, 50, dc_t, ac_t,
                  tile_sizes=(1, 2, 3, 5, 8, 13, 31, 64, 257))

    def test_truncated_streams_rejected_identically(self):
        rng = np.random.default_rng(6)
        dc, ac = _random_blocks(rng, 20)
        payload, dc_t, ac_t = _encode(dc, ac)

        def result(fn):
            try:
                dc_o, ac_o = fn()
                return ("ok", dc_o.tobytes(), ac_o.tobytes())
            except (bitio.TruncatedStream, ValueError) as e:
                return (type(e).__name__, str(e))

        for cut in (0, 1, 2, len(payload) // 2, len(payload) - 1):
            want = result(lambda: rle.decode_payload(
                payload[:cut], 20, dc_t, ac_t))
            for fn in (
                    lambda: unpack_ref.unpack_bits_ref(
                        payload[:cut], 20, dc_t, ac_t),
                    lambda: unpack_ref.unpack_bits_ref(
                        payload[:cut], 20, dc_t, ac_t, tile_bits=17),
                    lambda: unpack_bits.unpack_bits(
                        payload[:cut], 20, dc_t, ac_t, backend="pallas",
                        interpret=True)):
                assert result(fn) == want
        # over-claimed block count walks into the 1-padding: same error
        want = result(lambda: rle.decode_payload(payload, 21, dc_t, ac_t))
        got = result(lambda: unpack_bits.unpack_bits(
            payload, 21, dc_t, ac_t, backend="pallas", interpret=True))
        assert got == want and want[0] != "ok"

    def test_out_of_spec_dc_table_rejected(self):
        # a "DC" table coding symbol 16 is not a magnitude-category
        # alphabet; every backend rejects it up front like the walk
        bad_dc = huffman.build_table(
            np.bincount([0, 1, 16, 16], minlength=17))
        ac_t = huffman.STANDARD_AC_LUMA
        for fn in (rle.decode_payload, unpack_ref.unpack_bits_ref,
                   lambda *a: unpack_bits.unpack_bits(
                       *a, backend="pallas", interpret=True)):
            with pytest.raises(ValueError, match="magnitude-category"):
                fn(b"\x00", 1, bad_dc, ac_t)

    def test_oversize_stream_falls_back_to_reference(self, monkeypatch):
        # payloads past the VMEM guard must quietly take the NumPy path
        from repro.kernels.unpack_bits import ops
        monkeypatch.setattr(ops, "MAX_DEVICE_BITS", 64)
        rng = np.random.default_rng(7)
        dc, ac = _random_blocks(rng, 30)
        payload, dc_t, ac_t = _encode(dc, ac)
        assert len(payload) * 8 > 64
        want = rle.decode_payload_reference(payload, 30, dc_t, ac_t)
        got = unpack_bits.unpack_bits(payload, 30, dc_t, ac_t,
                                      backend="pallas", interpret=True)
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])

    def test_backend_selection(self, pallas_route):
        # off-TPU "auto" resolves to the NumPy reference, and the decode
        # route keeps the LUT walk; pinned, it is the device decode
        assert unpack_bits.select_backend("auto") in unpack_bits.BACKENDS
        if jax.default_backend() != "tpu":
            assert unpack_bits.select_backend("auto") == "numpy"
            assert unpack_bits.make_unpacker() is None
        pallas_route("unpack")
        assert unpack_bits.make_unpacker() is not None

    def test_scratch_is_bounded_by_tile_not_payload(self):
        # the staged decoder's memory claim: scratch saturates at one
        # tile + margin while the LUT walk's tables keep growing
        one_tile = unpack_ref.scratch_nbytes(unpack_ref.TILE_BITS)
        assert unpack_ref.scratch_nbytes(64 * unpack_ref.TILE_BITS) \
            == unpack_ref.scratch_nbytes(8 * unpack_ref.TILE_BITS)
        assert unpack_ref.scratch_nbytes(1 << 22) < 2 * one_tile
        assert rle.walk_table_nbytes(1 << 24) > \
            3 * rle.walk_table_nbytes(1 << 22)


def _hand_stream(blocks, dc_t=huffman.STANDARD_DC_LUMA,
                 ac_t=huffman.STANDARD_AC_LUMA):
    """Pack blocks given as symbol lists — ``(dc_category, dc_bits,
    [(ac_symbol, amplitude_bits), ...])`` — verbatim, so streams the
    encoder never writes (a ZRL past position 63, a run overrunning the
    block) can be built."""
    fields, widths = [], []

    def put(table, sym, bits, size):
        code_of, len_of = table.encoder_luts()
        fields.append(code_of[sym])
        widths.append(len_of[sym])
        if size:
            fields.append(bits)
            widths.append(size)

    for cat, bits, units in blocks:
        put(dc_t, cat, bits, cat)
        for sym, amp in units:
            put(ac_t, sym, amp, sym & 0xF if sym != rle.ZRL else 0)
    return bitio.pack_bits(np.asarray(fields), np.asarray(widths))


def _stage_streams():
    """Named payloads for the device stage: real encodes at the chain
    walk's extremes, and malformed streams."""
    dc_t, ac_t = huffman.STANDARD_DC_LUMA, huffman.STANDARD_AC_LUMA
    rng = np.random.default_rng(11)
    # a lone coefficient at column 62: three ZRLs and a run-14 symbol
    zrl = np.zeros((20, 63), np.int64)
    zrl[:, 62] = 7
    # 63 run-0 coefficients: the walk's 63-unit worst case
    dense = rng.integers(1, 500, (6, 63))
    wide = np.zeros((8, 63), np.int64)
    wide[:, rng.choice(63, 8, replace=False)] = 32767
    wide[:, 0] = -32767
    random_dc, random_ac = _random_blocks(rng, 60)
    valid = _encode(random_dc, random_ac)[0]
    return {
        "zrl_chains": _encode(np.zeros(20), zrl, std_tables=False),
        # four ZRLs cover 64 positions: the chain crosses 63 on a ZRL
        "zrl_overshoot": (_hand_stream([(0, 0, [(rle.ZRL, 0)] * 4)] * 5),
                          dc_t, ac_t),
        "run_overruns_block": (_hand_stream(
            [(0, 0, [(rle.ZRL, 0)] * 3 + [(0xF1, 1)])] * 3), dc_t, ac_t),
        "dense_63_units": _encode(rng.integers(-500, 500, 6), dense),
        "category_15": _encode(rng.choice([-32767, 32767], 8), wide,
                               std_tables=False),
        "truncated": (valid[:len(valid) // 2 + 1], dc_t, ac_t),
        "invalid_prefixes": (rng.integers(0, 256, 300, np.uint8).tobytes(),
                             dc_t, ac_t),
        "all_ones": (b"\xff" * 64, dc_t, ac_t),
    }


STAGE_STREAMS = _stage_streams()


class TestUnpackStage:
    """The device stage (unit words and the chain walk over the whole
    payload) against the NumPy stage of one tile covering it."""

    @staticmethod
    def _check(payload, dc_t, ac_t):
        from repro.kernels.unpack_bits import ops
        nbits = len(payload) * 8
        win, dcw, acw, outc = ops.stage(payload, nbits, dc_t, ac_t,
                                        interpret=True)
        assert dcw.size >= nbits + 1 + unpack_bits.kernel.MAX_ADV
        want = [unpack_ref._unit_words(win, nbits, 0, nbits + 1,
                                       *huffman.decoder_luts(t))
                for t in (dc_t, ac_t)]
        np.testing.assert_array_equal(dcw[:nbits + 1], want[0])
        np.testing.assert_array_equal(acw[:nbits + 1], want[1])
        np.testing.assert_array_equal(
            outc[:nbits + 1], unpack_ref._ac_outcomes(want[1], 0))
        return outc[:nbits + 1], dcw.size

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=6, deadline=None)
    def test_random_streams(self, seed):
        rng = np.random.default_rng(seed)
        n = int(rng.integers(1, 120))
        dc, ac = _random_blocks(rng, n)
        self._check(*_encode(dc, ac, std_tables=bool(n % 2)))

    @given(st.binary(min_size=1, max_size=200))
    @settings(max_examples=6, deadline=None)
    def test_random_bytes(self, payload):
        self._check(payload, huffman.STANDARD_DC_LUMA,
                    huffman.STANDARD_AC_LUMA)

    @pytest.mark.parametrize("name", sorted(STAGE_STREAMS))
    def test_named_streams(self, name):
        outc, _ = self._check(*STAGE_STREAMS[name])
        kind = {"zrl_overshoot": 0, "run_overruns_block": 3}.get(name)
        if kind is not None:
            # the first block's AC chain (after a 2-bit DC category 0)
            # crosses position 63 as built
            assert outc[2] & 3 == kind

    @pytest.mark.parametrize("nbytes", [252, 253])
    def test_payload_at_the_pow2_edge(self, nbytes):
        # 252 bytes fill the 2048 staged offsets exactly (2016 bits + the
        # end slot + 31 bits of reach); 253 bytes need the next bucket
        payload = np.random.default_rng(nbytes).integers(
            0, 256, nbytes, np.uint8).tobytes()
        _, staged = self._check(payload, huffman.STANDARD_DC_LUMA,
                                huffman.STANDARD_AC_LUMA)
        assert staged == (2048 if nbytes == 252 else 4096)


class TestUnpackOneTile:
    """``tile_bits=None`` (the engine's route): the payload resolves as
    one tile, with the reference's values and errors."""

    @staticmethod
    def _result(fn):
        try:
            dc, ac = fn()
            return ("ok", dc.tobytes(), ac.tobytes())
        except (bitio.TruncatedStream, ValueError) as e:
            return (type(e).__name__, str(e))

    @pytest.mark.parametrize("name", sorted(STAGE_STREAMS))
    @pytest.mark.parametrize("n_blocks", [1, 5, 40])
    def test_identical_to_reference(self, name, n_blocks):
        payload, dc_t, ac_t = STAGE_STREAMS[name]
        got = self._result(lambda: unpack_bits.unpack_bits(
            payload, n_blocks, dc_t, ac_t, backend="pallas",
            interpret=True))
        oracle = self._result(lambda: rle.decode_payload_reference(
            payload, n_blocks, dc_t, ac_t))
        # values, and whether the stream is rejected, match the scalar
        # oracle; the error and its bit offset match the LUT walk, whose
        # contract names truncation first where a unit both runs past
        # the payload and breaks another rule
        assert (got[0] == "ok") == (oracle[0] == "ok")
        if oracle[0] == "ok":
            assert got == oracle
        assert got == self._result(lambda: rle.decode_payload(
            payload, n_blocks, dc_t, ac_t))

    def test_resolves_one_tile(self, monkeypatch):
        # the engine's route resolves on the device and never calls the
        # host resolver; over the block guard the host resolves the
        # payload as one tile, and an explicit tile_bits in several
        from repro.kernels.unpack_bits import ops
        seen = []
        real = unpack_ref.resolve

        def spy(win, nbits, n_blocks, tile_bits, get_tile):
            def counted(t):
                seen.append(t)
                return get_tile(t)
            return real(win, nbits, n_blocks, tile_bits, counted)

        monkeypatch.setattr(ops.ref, "resolve", spy)
        rng = np.random.default_rng(13)
        dc, ac = _random_blocks(rng, 80)
        payload, dc_t, ac_t = _encode(dc, ac)
        want = rle.decode_payload_reference(payload, 80, dc_t, ac_t)
        got = unpack_bits.unpack_bits(payload, 80, dc_t, ac_t,
                                      backend="pallas", interpret=True)
        np.testing.assert_array_equal(got[1], want[1])
        assert seen == []
        monkeypatch.setattr(ops, "MAX_DEVICE_BLOCKS", 79)
        got = unpack_bits.unpack_bits(payload, 80, dc_t, ac_t,
                                      backend="pallas", interpret=True)
        np.testing.assert_array_equal(got[1], want[1])
        assert seen == [0]
        seen.clear()
        unpack_bits.unpack_bits(payload, 80, dc_t, ac_t, backend="pallas",
                                tile_bits=256, interpret=True)
        assert len(set(seen)) > 1


COLOUR = container.COLOUR_BLOCK_CLASSES


def _encode_classes(dc_diff, ac, classes=COLOUR, std_tables=True):
    """Blocks of a two-class stream -> (payload, dc_tables, ac_tables)."""
    prep = dense.prepare(np.asarray(dc_diff, np.int64),
                         np.asarray(ac, np.int64), classes=classes)
    if std_tables:
        dcs = tuple(huffman.DEFAULT_TABLES.get(d)
                    for d, _ in huffman.STANDARD_IDS)
        acs = tuple(huffman.DEFAULT_TABLES.get(a)
                    for _, a in huffman.STANDARD_IDS)
    else:
        dcs = tuple(huffman.build_table(f) for f in prep.dc_freq)
        acs = tuple(huffman.build_table(f) for f in prep.ac_freq)
    return prep.payload(dcs, acs), dcs, acs


def _outcome(fn):
    try:
        dc, ac = fn()
        return ("ok", np.asarray(dc, np.int32).tobytes(),
                np.asarray(ac, np.int32).tobytes())
    except (bitio.TruncatedStream, ValueError) as e:
        return (type(e).__name__, str(e))


def _moved(key, fn):
    """``(fn(), how far counter ``key`` moved)``."""
    before = obs.counts().get(key, 0)
    out = fn()
    return out, obs.counts().get(key, 0) - before


class TestResolveOnDevice:
    """The engine's route (``tile_bits=None``): the chain resolves and
    the values are emitted on the device, in interpret mode here, with
    the values and the errors (class and message) of ``ref.resolve``
    and of ``rle.decode_payload``."""

    @staticmethod
    def _check(payload, n_blocks, dc_t, ac_t, classes=rle.ONE_CLASS):
        extra = {} if classes == rle.ONE_CLASS else {"classes": classes}
        got, moved = _moved("entropy.resolve.device", lambda: _outcome(
            lambda: unpack_bits.unpack_bits(payload, n_blocks, dc_t, ac_t,
                                            backend="pallas",
                                            interpret=True, **extra)))
        assert moved == 1
        assert got == _outcome(lambda: unpack_ref.unpack_bits_ref(
            payload, n_blocks, dc_t, ac_t, **extra))
        assert got == _outcome(lambda: rle.decode_payload(
            payload, n_blocks, dc_t, ac_t, **extra))
        return got

    @pytest.mark.parametrize("name", sorted(STAGE_STREAMS))
    @pytest.mark.parametrize("n_blocks", [15, 16, 17])
    def test_named_streams(self, name, n_blocks):
        # either side of the 16 blocks of one output tile
        self._check(STAGE_STREAMS[name][0], n_blocks,
                    *STAGE_STREAMS[name][1:])

    @given(st.integers(0, 2**31 - 1), st.integers(-2, 1))
    @settings(max_examples=8, deadline=None)
    def test_random_streams(self, seed, claim):
        # valid streams, with one or two blocks too few or one too many
        # claimed (the padding then has to fail as the reference fails)
        rng = np.random.default_rng(seed)
        n = int(rng.integers(3, 150))
        dc, ac = _random_blocks(rng, n)
        payload, dc_t, ac_t = _encode(dc, ac, std_tables=bool(n % 2))
        got = self._check(payload, n + claim, dc_t, ac_t)
        assert (got[0] == "ok") == (claim <= 0)

    @given(st.binary(min_size=1, max_size=200), st.integers(1, 60))
    @settings(max_examples=8, deadline=None)
    def test_random_bytes(self, payload, n_blocks):
        self._check(payload, n_blocks, huffman.STANDARD_DC_LUMA,
                    huffman.STANDARD_AC_LUMA)

    @pytest.mark.parametrize("n_blocks", [1023, 1024, 1025])
    def test_block_bucket_edge(self, n_blocks):
        # either side of the output's 1,024-block bucket, over a payload
        # of several SMEM windows, so block chains cross window edges
        from repro.kernels.unpack_bits import kernel, ops
        rng = np.random.default_rng(n_blocks)
        dc = rng.integers(-60, 61, n_blocks)
        ac = np.zeros((n_blocks, 63), np.int64)
        ac[:, :3] = rng.integers(-9, 10, (n_blocks, 3))
        payload, dc_t, ac_t = _encode(dc, ac)
        assert len(payload) * 8 > 2 * kernel.RESOLVE_WORDS
        assert ops.BLOCK_BUCKET == 1024
        got = self._check(payload, n_blocks, dc_t, ac_t)
        assert got[0] == "ok"

    @given(st.integers(0, 2**31 - 1), st.integers(-1, 1))
    @settings(max_examples=6, deadline=None)
    def test_two_class_streams(self, seed, claim):
        # colour payloads: blocks take the tables of class
        # (0, 0, 0, 0, 1, 1)[b % 6]
        rng = np.random.default_rng(seed)
        n = int(rng.integers(6, 120))      # both classes code blocks
        dc, ac = _random_blocks(rng, n, hi=500)
        payload, dcs, acs = _encode_classes(dc, ac, std_tables=bool(n % 2))
        got = self._check(payload, n + claim, dcs, acs, COLOUR)
        assert (got[0] == "ok") == (claim <= 0)

    def test_two_class_windows_and_truncation(self):
        # a two-class payload over several windows, whole and cut short
        from repro.kernels.unpack_bits import kernel
        rng = np.random.default_rng(21)
        n = 700
        dc, ac = _random_blocks(rng, n, hi=200)
        payload, dcs, acs = _encode_classes(dc, ac, std_tables=False)
        assert len(payload) * 8 > kernel.RESOLVE_WORDS
        assert self._check(payload, n, dcs, acs, COLOUR)[0] == "ok"
        for cut in (1, len(payload) // 3, len(payload) - 1):
            assert self._check(payload[:cut], n, dcs, acs,
                               COLOUR)[0] != "ok"

    def test_routes_counted(self, monkeypatch):
        # one resolve route per stream: the device on the engine's
        # route; the host for an explicit tile_bits, over the block or
        # class guard, and on the numpy backend
        from repro.kernels.unpack_bits import ops
        rng = np.random.default_rng(23)
        dc, ac = _random_blocks(rng, 30)
        payload, dc_t, ac_t = _encode(dc, ac)
        cpayload, dcs, acs = _encode_classes(dc, ac)

        def run(**kw):
            p, d, a = (cpayload, dcs, acs) if "classes" in kw else \
                (payload, dc_t, ac_t)
            kw.setdefault("backend", "pallas")
            before = obs.counts()
            unpack_bits.unpack_bits(p, 30, d, a, interpret=True, **kw)
            return {k: obs.counts().get(k, 0) - before.get(k, 0)
                    for k in ("entropy.resolve.device",
                              "entropy.resolve.host")}

        device = {"entropy.resolve.device": 1, "entropy.resolve.host": 0}
        host = {"entropy.resolve.device": 0, "entropy.resolve.host": 1}
        assert run() == device
        assert run(classes=COLOUR) == device
        assert run(tile_bits=256) == host
        assert run(backend="numpy") == host
        monkeypatch.setattr(ops, "MAX_DEVICE_CLASSES", 1)
        assert run(classes=COLOUR) == host
        assert run() == device
        monkeypatch.setattr(ops, "MAX_DEVICE_BLOCKS", 29)
        assert run() == host


class TestUnpackThroughContainer:
    def test_golden_fixtures_identical_across_backends(self):
        from repro.core import entropy
        for f in sorted(DATA_DIR.glob("*.dctz")):
            data = f.read_bytes()
            z0, h0 = entropy.decode_zigzag_host(data)
            for up in (lambda *a: unpack_bits.unpack_bits(
                           *a, backend="pallas", interpret=True),
                       lambda *a: unpack_bits.unpack_bits(
                           *a, backend="numpy")):
                z1, h1 = entropy.decode_zigzag_host(data, unpacker=up)
                np.testing.assert_array_equal(z0, z1, err_msg=f.name)
                assert h0 == h1

    def test_decode_image_with_unpacker(self):
        from repro.core import entropy, images
        img = np.asarray(images.lena_like(48, 56))
        blob = entropy.encode_image(img, quality=50)
        base = np.asarray(entropy.decode_image(blob))
        routed = np.asarray(entropy.decode_image(
            blob, unpacker=lambda *a: unpack_bits.unpack_bits(
                *a, backend="pallas", interpret=True)))
        np.testing.assert_array_equal(base, routed)
