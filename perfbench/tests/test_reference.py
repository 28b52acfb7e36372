"""The plain reference: its decoder reads its encoder's streams, the
CORDIC graph with an exact budget is the DCT, and its levels agree with
the float transform up to rounding ties."""

import numpy as np
import pytest

from perfbench import checks, images, reference as ref


@pytest.mark.parametrize("shape,quality", [((64, 64), 75), ((40, 56), 50),
                                           ((17, 9), 10)])
def test_encode_parse_roundtrip(shape, quality):
    img = images.cablecar_like(*shape, seed=4)
    levels = ref.encode_levels(img, quality)
    hdr, back = ref.parse_dctz(ref.encode_dctz(levels, quality, "exact",
                                               shape))
    assert (hdr["height"], hdr["width"], hdr["quality"]) == (*shape, quality)
    np.testing.assert_array_equal(back, levels)


def test_parse_rejects_a_flipped_byte():
    img = images.lena_like(32, 32, seed=1)
    blob = bytearray(ref.encode_dctz(ref.encode_levels(img, 50), 50,
                                     "exact", (32, 32)))
    blob[-1] ^= 0x01
    with pytest.raises(ref.StreamError):
        ref.parse_dctz(bytes(blob))


def test_exact_transform_is_orthonormal():
    c = ref.dct_matrix()
    np.testing.assert_allclose(c @ c.T, np.eye(8), atol=1e-12)
    x = np.random.default_rng(0).normal(size=(3, 8, 8))
    np.testing.assert_allclose(ref.inverse(ref.forward(x, "exact"), "exact"),
                               x, atol=1e-10)


def test_cordic_stays_near_exact():
    x = np.random.default_rng(1).uniform(-128, 127, size=(50, 8, 8))
    exact, cordic = ref.forward(x, "exact"), ref.forward(x, "cordic")
    # the paper's low-power budget: errors of a few grid steps, not more
    assert np.abs(exact - cordic).max() < 8 * ref.CORDIC_GRID
    assert np.all(cordic % ref.CORDIC_GRID == 0)


def test_gaps_are_zero_for_the_reference_itself():
    img = images.lena_like(48, 40, seed=2)
    tally = checks.Tally({"undecodable": 0, "header_mismatch": 0,
                          "level_gap": 0.0, "pixel_gap": 0.0})
    levels = ref.encode_levels(img, 60)
    blob = ref.encode_dctz(levels, 60, "exact", img.shape)
    rec = ref.decode_pixels(levels, 60, img.shape)
    checks.check_stream(tally, img, blob, 60, "exact", rec=rec)
    assert tally.correct, tally.report()


def test_image_source_never_repeats():
    src = images.ImageSource([{"generator": "lena_like", "height": 16,
                               "width": 16}], seed=2**31 + 5)
    seen = {src.image(i).tobytes() for i in range(2000)}
    assert len(seen) == 2000
    again = images.ImageSource(src.kinds, seed=2**31 + 5)
    np.testing.assert_array_equal(again.image(77), src.image(77))
