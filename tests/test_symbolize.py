"""Symbolize kernel triplet: oracle identity, routing, and the
histogram -> table-negotiation -> bytes chain.

The load-bearing property (the last test class): for every routed
symbolize backend the device/staged histograms equal the host
histograms **bit-for-bit** as int64 arrays, therefore
:func:`repro.core.entropy.huffman.build_table_memo` — keyed on the raw
histogram bytes — returns the *identical* memoised table object,
therefore ``tables="auto"`` negotiates the same table ids and the
framed ``DCTZ`` streams come out byte-identical.  That chain is what
lets the engine's two symbolize routes (the device on a TPU within
its guards, the host symbolizer elsewhere) share one wire format.
"""

import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.entropy import container, huffman, rle
from repro.kernels.symbolize import MAX_DEVICE_BLOCKS, ops
from repro.kernels.symbolize import ref as sref

BACKENDS = ("numpy", "pallas")


def _backend_kwargs(backend):
    # interpret=True keeps the Pallas leg runnable off-TPU
    return {"backend": backend, "interpret": True}


DENSITIES = (0.02, 0.15, 0.6)


def _rand_blocks(n, seed, density, max_mag=255):
    rng = np.random.default_rng(seed)
    dc_diff = rng.integers(-max_mag, max_mag + 1, n)
    ac = rng.integers(-max_mag, max_mag + 1, (n, 63))
    ac[rng.uniform(size=ac.shape) >= density] = 0
    return dc_diff, ac


def _adversarial():
    """Hand-built blocks hitting every structural edge at once."""
    rows = [
        np.zeros(63, np.int64),                      # all-zero: DC + EOB
        np.r_[np.zeros(62, np.int64), 7],            # 3 ZRLs, no EOB
        np.ones(63, np.int64),                       # dense, no runs
        np.r_[5, np.zeros(61, np.int64), -1],        # leading + trailing
        np.full(63, 32767, np.int64),                # max 15-bit amplitude
        np.full(63, -32767, np.int64),
    ]
    ac = np.stack(rows)
    dc = np.array([0, 32767, -32767, 1, -1, 16], np.int64)
    return dc, ac


# ---------------------------------------------------------------------------
# stream/element identity against the scalar oracle
# ---------------------------------------------------------------------------

class TestOracleIdentity:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 10), st.integers(0, 2**31 - 1),
           st.sampled_from(DENSITIES))
    def test_staged_ref_matches_oracle(self, n, seed, density):
        dc_diff, ac = _rand_blocks(n, seed, density)
        want = rle.symbolize_reference(dc_diff, ac)
        got = sref.symbolize_ref(dc_diff, ac)
        for w, g in zip(want, got):
            assert w.dtype == g.dtype
            assert np.array_equal(w, g)

    @settings(max_examples=10, deadline=None)
    @given(st.integers(1, 6), st.integers(0, 2**31 - 1),
           st.sampled_from(DENSITIES))
    def test_routed_backends_match_oracle(self, n, seed, density):
        dc_diff, ac = _rand_blocks(n, seed, density)
        want = rle.symbolize_reference(dc_diff, ac)
        for backend in BACKENDS:
            got = ops.symbolize(dc_diff, ac, **_backend_kwargs(backend))
            for w, g in zip(want, got):
                assert w.dtype == g.dtype
                assert np.array_equal(w, g)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_adversarial_blocks(self, backend):
        dc_diff, ac = _adversarial()
        want = rle.symbolize_reference(dc_diff, ac)
        got = ops.symbolize(dc_diff, ac, **_backend_kwargs(backend))
        for w, g in zip(want, got):
            assert np.array_equal(w, g)

    def test_empty_stream(self):
        dc_diff = np.zeros(0, np.int64)
        ac = np.zeros((0, 63), np.int64)
        want = rle.symbolize_reference(dc_diff, ac)
        got = ops.symbolize(dc_diff, ac, backend="numpy")
        for w, g in zip(want, got):
            assert w.dtype == g.dtype and w.shape == g.shape

    def test_shape_mismatch_rejected(self):
        with pytest.raises(ValueError, match="does not match"):
            sref.symbolize_dense(np.zeros(2, np.int64),
                                 np.zeros((3, 63), np.int64))


# ---------------------------------------------------------------------------
# range guards: oracle-exact RangeError from every backend
# ---------------------------------------------------------------------------

class TestRangeErrors:
    @pytest.mark.parametrize("backend", BACKENDS)
    def test_dc_overflow_message_identical(self, backend):
        dc = np.array([1 << 15], np.int64)
        ac = np.zeros((1, 63), np.int64)
        with pytest.raises(rle.RangeError) as oracle:
            rle.symbolize_reference(dc, ac)
        with pytest.raises(rle.RangeError) as routed:
            ops.symbolize(dc, ac, **_backend_kwargs(backend))
        assert str(routed.value) == str(oracle.value)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_ac_overflow_message_identical(self, backend):
        dc = np.zeros(1, np.int64)
        ac = np.zeros((1, 63), np.int64)
        ac[0, 5] = -(1 << 15)
        with pytest.raises(rle.RangeError) as oracle:
            rle.symbolize_reference(dc, ac)
        with pytest.raises(rle.RangeError) as routed:
            ops.symbolize(dc, ac, **_backend_kwargs(backend))
        assert str(routed.value) == str(oracle.value)


# ---------------------------------------------------------------------------
# routing and guard fallbacks
# ---------------------------------------------------------------------------

class TestRouting:
    def test_auto_is_numpy_off_tpu(self):
        import jax
        want = "pallas" if jax.default_backend() == "tpu" else "numpy"
        assert ops.select_backend("auto") == want

    def test_unknown_backend_rejected(self):
        with pytest.raises(ValueError, match="backend"):
            ops.select_backend("cuda")

    def test_oversized_batch_falls_back_to_ref(self):
        # past the device ceiling the pallas route must still answer —
        # via the staged host pass — with oracle-identical output
        n = MAX_DEVICE_BLOCKS + 1
        dc_diff = np.ones(n, np.int64)
        ac = np.zeros((n, 63), np.int64)
        ac[:, 0] = -3
        want = rle.symbolize_reference(dc_diff, ac)
        got = ops.symbolize(dc_diff, ac, backend="pallas", interpret=True)
        for w, g in zip(want, got):
            assert np.array_equal(w, g)


# ---------------------------------------------------------------------------
# the symbolizer protocol: histograms -> memoised tables -> bytes
# ---------------------------------------------------------------------------

class TestTableNegotiationChain:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(1, 6), st.integers(0, 2**31 - 1),
           st.sampled_from(DENSITIES))
    def test_histograms_bit_for_bit_and_memo_key_identity(self, n, seed,
                                                          density):
        dc_diff, ac = _rand_blocks(n, seed, density)
        is_dc, syms, _, _ = rle.symbolize_reference(dc_diff, ac)
        host_dc, host_ac = rle.symbol_frequencies(is_dc, syms)
        for backend in BACKENDS:
            dense = ops.symbolize_dense(dc_diff, ac,
                                        **_backend_kwargs(backend))
            for got, want in ((dense.dc_freq, host_dc),
                              (dense.ac_freq, host_ac)):
                got = np.asarray(got)
                assert got.dtype == np.int64
                assert np.array_equal(got, want)
            # bit-identical int64 histograms -> identical memo key ->
            # build_table_memo returns the very same table object, so
            # "auto" negotiation cannot diverge between backends
            assert (huffman.build_table_memo(dense.dc_freq)
                    is huffman.build_table_memo(host_dc))
            assert (huffman.build_table_memo(dense.ac_freq)
                    is huffman.build_table_memo(host_ac))

    @settings(max_examples=8, deadline=None)
    @given(st.integers(1, 6), st.integers(0, 2**31 - 1),
           st.sampled_from(DENSITIES))
    def test_auto_negotiated_streams_byte_identical(self, n, seed,
                                                    density):
        dc_diff, ac = _rand_blocks(n, seed, density, max_mag=100)
        dc = np.cumsum(dc_diff)
        z = np.concatenate([dc[:, None], ac], axis=1)
        shape = (8, 8 * n)                       # 1 x n block grid
        want = container.encode_zigzag_host(z, 50, "exact", shape,
                                            tables="auto")
        hdr = container.read_header(want)
        for backend in BACKENDS:
            symbolizer = functools.partial(ops.prepare, backend=backend,
                                           interpret=True)
            got = container.encode_zigzag_host(z, 50, "exact", shape,
                                               tables="auto",
                                               symbolizer=symbolizer)
            got_hdr = container.read_header(got)
            assert (got_hdr["dc_table_id"], got_hdr["ac_table_id"]) == \
                (hdr["dc_table_id"], hdr["ac_table_id"])
            assert got == want
        qc, _ = container.decode_qcoeffs(want)
        assert qc.shape == (1, n, 8, 8)

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_payload_matches_encode_payload(self, backend):
        dc_diff, ac = _adversarial()
        # clamp to keep every amplitude codable by the standard tables
        ac = np.clip(ac, -1023, 1023)
        dc_diff = np.clip(dc_diff, -1023, 1023)
        stream = rle.symbolize_reference(dc_diff, ac)
        dc_t = huffman.DEFAULT_TABLES.get(huffman.STANDARD_DC_LUMA_ID)
        ac_t = huffman.DEFAULT_TABLES.get(huffman.STANDARD_AC_LUMA_ID)
        want = rle.encode_payload(*stream, dc_t, ac_t)
        prep = ops.prepare(dc_diff, ac, backend=backend, interpret=True)
        assert prep.payload(dc_t, ac_t) == want

    @pytest.mark.parametrize("backend", BACKENDS)
    def test_uncodable_symbol_error_identical(self, backend):
        # a table that cannot code the stream must raise the same
        # ValueError as rle.codeword_fields
        dc_diff = np.array([3], np.int64)
        ac = np.zeros((1, 63), np.int64)
        tiny = huffman.build_table(
            np.bincount([rle.EOB], minlength=256))  # codes only EOB
        stream = rle.symbolize_reference(dc_diff, ac)
        with pytest.raises(ValueError) as oracle:
            rle.encode_payload(*stream, tiny, tiny)
        prep = ops.prepare(dc_diff, ac, backend=backend, interpret=True)
        with pytest.raises(ValueError) as routed:
            prep.payload(tiny, tiny)
        assert str(routed.value) == str(oracle.value)
