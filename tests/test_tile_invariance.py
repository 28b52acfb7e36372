"""Tile-invariance property tests: the gate that makes autotuning safe.

For every pow2 candidate the autotuner may select
(:data:`repro.bench.autotune.CANDIDATES`), the image kernels must be
**bit-exact** across tile sizes and the bit-stream kernels must stay
byte/error-identical to their scalar references at non-default
``tile_bits`` — so a tuning artifact can only ever change speed, never
output.  Plus the :func:`repro.kernels.common.pick_tile` boundary
behaviour the routers rely on (dims 8/16, non-pow2 padded shapes,
dim <= 0 rejection)."""

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.bench.autotune import CANDIDATES
from repro.kernels import common

# Non-pow2 sizes pad to tile multiples inside the routers (100 -> 104);
# kept small so the full candidate sweep stays tier-1 fast.
IMAGE_SIZES = (24, 64, 100)
REFERENCE_TILE = 256


def _image(size: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 255.0, (size, size)).astype(np.float32)


# ---------------------------------------------------------------------------
# Image kernels: bit-exact across every tile candidate
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(st.sampled_from(CANDIDATES["dct8x8"]),
       st.sampled_from(IMAGE_SIZES), st.integers(0, 3))
def test_dct8x8_tile_invariant(tile, size, seed):
    from repro.kernels.dct8x8 import ops
    x = _image(size, seed)
    want = np.asarray(ops.dct8x8(x, tile=REFERENCE_TILE))
    got = np.asarray(ops.dct8x8(x, tile=tile))
    assert np.array_equal(got, want), f"dct8x8 tile={tile} size={size}"
    coeffs = want
    want_inv = np.asarray(ops.idct8x8(coeffs, tile=REFERENCE_TILE))
    got_inv = np.asarray(ops.idct8x8(coeffs, tile=tile))
    assert np.array_equal(got_inv, want_inv), \
        f"idct8x8 tile={tile} size={size}"


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(CANDIDATES["cordic_loeffler"]),
       st.sampled_from(IMAGE_SIZES), st.integers(0, 3))
def test_cordic_loeffler_tile_invariant(tile, size, seed):
    from repro.kernels.cordic_loeffler import ops
    x = _image(size, seed)
    want = np.asarray(ops.cordic_loeffler_dct(x, tile=REFERENCE_TILE))
    got = np.asarray(ops.cordic_loeffler_dct(x, tile=tile))
    assert np.array_equal(got, want), f"cordic tile={tile} size={size}"


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(CANDIDATES["fused_codec"]),
       st.sampled_from(IMAGE_SIZES), st.integers(0, 3))
def test_fused_codec_tile_invariant(tile, size, seed):
    from repro.kernels.fused_codec import ops
    x = _image(size, seed)
    want_rec, want_qc = ops.fused_codec(x, tile=REFERENCE_TILE)
    got_rec, got_qc = ops.fused_codec(x, tile=tile)
    assert np.array_equal(np.asarray(got_rec), np.asarray(want_rec)), \
        f"fused_codec rec tile={tile} size={size}"
    assert np.array_equal(np.asarray(got_qc), np.asarray(want_qc)), \
        f"fused_codec qc tile={tile} size={size}"


# ---------------------------------------------------------------------------
# pack_bits: byte-identical to the scalar reference at every tile_bits
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.sampled_from(CANDIDATES["pack_bits"]),
       st.integers(0, 400), st.integers(0, 3))
def test_pack_bits_tile_bits_invariant(tile_bits, n_fields, seed):
    from repro.core.entropy import bitio
    from repro.kernels.pack_bits import ops
    rng = np.random.default_rng(seed * 1000 + n_fields)
    lengths = rng.integers(0, 17, n_fields)         # zero-width included
    codes = rng.integers(0, 1 << 16, n_fields) & ((1 << lengths) - 1)
    want = bitio.pack_bits(codes, lengths)
    got = ops.pack_bits(codes, lengths, backend="pallas",
                        tile_bits=tile_bits, interpret=True)
    assert got == want, f"pack_bits tile_bits={tile_bits} n={n_fields}"


# ---------------------------------------------------------------------------
# unpack_bits: value- and error-identical to the scalar oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def entropy_payload():
    """One real entropy stream (image -> zig-zag -> symbols -> payload)."""
    from repro.bench.cases import _entropy_stage_inputs
    (_, _, _, payload, (dc_t, ac_t),
     n_blocks) = _entropy_stage_inputs(32)
    return payload, n_blocks, dc_t, ac_t


def _outcome(fn, *args, **kw):
    from repro.core.entropy import bitio
    try:
        dc, ac = fn(*args, **kw)
        return ("ok", dc.tobytes(), ac.tobytes())
    except (bitio.TruncatedStream, ValueError) as e:
        return (type(e).__name__, str(e))


@pytest.mark.parametrize("tile_bits", CANDIDATES["unpack_bits"])
def test_unpack_bits_tile_bits_invariant(tile_bits, entropy_payload):
    from repro.core.entropy import rle
    from repro.kernels.unpack_bits import ops
    payload, n_blocks, dc_t, ac_t = entropy_payload
    want = _outcome(rle.decode_payload_reference, payload, n_blocks,
                    dc_t, ac_t)
    got = _outcome(ops.unpack_bits, payload, n_blocks, dc_t, ac_t,
                   backend="pallas", tile_bits=tile_bits, interpret=True)
    assert got == want, f"unpack_bits tile_bits={tile_bits}"


@pytest.mark.parametrize("tile_bits", (CANDIDATES["unpack_bits"][0],
                                       CANDIDATES["unpack_bits"][-1]))
def test_unpack_bits_truncation_errors_tile_invariant(tile_bits,
                                                      entropy_payload):
    from repro.core.entropy import rle
    from repro.kernels.unpack_bits import ops
    payload, n_blocks, dc_t, ac_t = entropy_payload
    for cut in (0, len(payload) // 2, len(payload) - 1):
        want = _outcome(rle.decode_payload, payload[:cut], n_blocks,
                        dc_t, ac_t)
        got = _outcome(ops.unpack_bits, payload[:cut], n_blocks, dc_t,
                       ac_t, backend="pallas", tile_bits=tile_bits,
                       interpret=True)
        assert got == want, \
            f"unpack_bits tile_bits={tile_bits} truncated at byte {cut}"


# ---------------------------------------------------------------------------
# symbolize: element-identical to the scalar oracle at every tile_blocks
# ---------------------------------------------------------------------------

def _blocks(n: int, seed: int):
    rng = np.random.default_rng(seed)
    dc_diff = rng.integers(-1024, 1025, n)
    ac = rng.integers(-255, 256, (n, 63))
    ac[rng.uniform(size=ac.shape) < 0.85] = 0     # realistic sparsity
    return dc_diff, ac


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(CANDIDATES["symbolize"]),
       st.integers(1, 40), st.integers(0, 3))
def test_symbolize_tile_blocks_invariant(tile_blocks, n, seed):
    from repro.core.entropy import rle
    from repro.kernels.symbolize import ops
    dc_diff, ac = _blocks(n, seed)
    want = rle.symbolize_reference(dc_diff, ac)
    got = ops.symbolize(dc_diff, ac, backend="pallas",
                        tile_blocks=tile_blocks, interpret=True)
    for w, g in zip(want, got):
        assert w.dtype == g.dtype and np.array_equal(w, g), \
            f"symbolize tile_blocks={tile_blocks} n={n}"
    dense = ops.symbolize_dense(dc_diff, ac, backend="pallas",
                                tile_blocks=tile_blocks, interpret=True)
    dc_freq, ac_freq = rle.symbol_frequencies(want[0], want[1])
    assert np.array_equal(dense.dc_freq, dc_freq)
    assert np.array_equal(dense.ac_freq, ac_freq)


# ---------------------------------------------------------------------------
# grad_dct: bit-exact across every block_rows candidate
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block_rows", CANDIDATES["grad_dct"])
def test_grad_dct_block_rows_invariant(block_rows):
    from repro.kernels import grad_dct as gd
    rng = np.random.default_rng(block_rows)
    g = rng.standard_normal(200 * gd.BLOCK + 9).astype(np.float32)
    ref_rows = CANDIDATES["grad_dct"][-1]
    want = gd.encode(g, block_rows=ref_rows, interpret=True)
    got = gd.encode(g, block_rows=block_rows, interpret=True)
    assert np.array_equal(np.asarray(got.q), np.asarray(want.q))
    assert np.array_equal(np.asarray(got.scale), np.asarray(want.scale))
    assert np.array_equal(np.asarray(got.tail), np.asarray(want.tail))
    want_g = np.asarray(gd.decode(want, block_rows=ref_rows,
                                  interpret=True))
    got_g = np.asarray(gd.decode(want, block_rows=block_rows,
                                 interpret=True))
    assert np.array_equal(got_g, want_g), \
        f"grad_dct decode block_rows={block_rows}"


def test_grad_dct_routes_tuned_block_rows(tmp_path, monkeypatch):
    # block_rows=None must consult the tuning artifact, like the other
    # kernel routers
    import json

    from repro.kernels import grad_dct as gd
    from repro.kernels import tuning
    doc = tuning.make_doc([{"kernel": "grad_dct", "bucket": 256,
                            "params": {"block_rows": 64},
                            "best_us": 1.0}], backend="cpu")
    p = tmp_path / "tuning.json"
    p.write_text(json.dumps(doc))
    monkeypatch.setenv("REPRO_TUNING_PATH", str(p))
    tuning.invalidate_cache()
    try:
        seen = {}
        real = gd.ops.kernel.grad_dct_encode_pallas

        def spy(body, c, *, keep, block_rows, interpret):
            seen["block_rows"] = block_rows
            return real(body, c, keep=keep, block_rows=block_rows,
                        interpret=interpret)

        monkeypatch.setattr(gd.ops.kernel, "grad_dct_encode_pallas", spy)
        g = np.ones(200 * gd.BLOCK, np.float32)
        gd.encode(g, interpret=True)
        assert seen["block_rows"] == 64
    finally:
        tuning.invalidate_cache()


# ---------------------------------------------------------------------------
# pick_tile boundary behaviour (the contract the routers rely on)
# ---------------------------------------------------------------------------

class TestPickTile:
    def test_dim_8(self):
        assert common.pick_tile(8) == 8
        assert common.pick_tile(8, target=8) == 8

    def test_dim_16(self):
        assert common.pick_tile(16) == 16
        assert common.pick_tile(16, target=8) == 8

    def test_non_pow2_padded_shapes(self):
        # 100 pads to 104 = 8 * 13: only 8, 104 divide it
        assert common.pick_tile(104, target=64) == 8
        assert common.pick_tile(104, target=104) == 104
        # 200 = 8 * 25: largest divisor <= 100 that is a multiple of 8
        assert common.pick_tile(200, target=100) == 40
        assert common.pick_tile(200) == 200

    def test_target_below_multiple_returns_multiple(self):
        # the tile must stay a multiple of 8 even when the target is
        # smaller: the worst case the docstring pins
        assert common.pick_tile(64, target=4) == 8
        assert common.pick_tile(64, target=0) == 8

    def test_nonpositive_dim_rejected(self):
        with pytest.raises(ValueError, match="positive"):
            common.pick_tile(0)
        with pytest.raises(ValueError, match="positive"):
            common.pick_tile(-8)

    def test_non_multiple_dim_rejected(self):
        with pytest.raises(ValueError, match="multiple"):
            common.pick_tile(12)

    def test_every_candidate_yields_valid_tile(self):
        # any pow2 target the autotuner may route resolves to a tile
        # that divides the padded dim — for every padded image size
        from repro.bench.autotune import CANDIDATES
        for size in (8, 16, 64, 104, 200, 256):
            for target in CANDIDATES["dct8x8"]:
                t = common.pick_tile(size, target)
                assert size % t == 0 and t % 8 == 0 and t <= max(target, 8)

    @pytest.mark.parametrize("width,target,want", [
        (480, 256, 480),     # no multiple of 128 divides 480: whole width
        (200, 256, 200),
        (104, 64, 104),
        (1024, 256, 256),
        (1024, 64, 128),     # never below one 128-lane vreg
        (1024, 1024, 1024),
    ])
    def test_lane_tile_is_128_multiple_or_full_width(self, width, target,
                                                     want):
        t = common.pick_lane_tile(width, target)
        assert t == want
        assert width % t == 0 and (t % 128 == 0 or t == width)

    @pytest.mark.parametrize("h,w", [(512, 480), (200, 200), (104, 104),
                                     (1024, 1024), (1024, 832)])
    def test_tile_shape_obeys_tpu_block_rule(self, h, w):
        for target in CANDIDATES["fused_codec"]:
            th, tw = common.tile_shape(h, w, target)
            assert h % th == 0 and th % 8 == 0
            assert w % tw == 0 and (tw % 128 == 0 or tw == w)
