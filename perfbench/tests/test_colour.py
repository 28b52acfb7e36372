"""The colour cell's yardstick, at a small size on the CPU: the colour
reference against its own independent decoder, the control (the
reference in the program's place: float32 passes, bfloat16 fails the
cell's limits), planted faults that come out not correct, the sound
program that comes out correct, and the traffic of the four-chip cell."""

import copy

import numpy as np
import pytest

from perfbench import colour, control, control_colour, run
from perfbench import reference_colour as rc
from perfbench.tests.small import execute

SMALL_KODAK = {
    "images": [{"generator": "lena_like", "height": 48, "width": 64},
               {"generator": "cablecar_like", "height": 48, "width": 64},
               {"generator": "lena_like", "height": 64, "width": 48}],
    "pixels_per_step": 3 * 48 * 64,
}


def small_kodak() -> run.Cell:
    cell = run.Cell("kodak420.codec")
    cell.config = copy.deepcopy(cell.config)
    cell.config["images"] = SMALL_KODAK["images"]
    cell.traffic = dict(cell.traffic,
                        pixels_per_step=SMALL_KODAK["pixels_per_step"])
    return cell


@pytest.mark.parametrize("shape,quality", [((48, 64), 75), ((37, 53), 50),
                                           ((64, 48), 90)])
def test_reference_decoder_reads_its_encoder(shape, quality):
    img = colour.colour_image("cablecar_like", *shape, seed=3)
    levels = rc.encode_levels(img, quality)
    blob = rc.encode_dctz3(levels, quality, "exact", shape)
    hdr, back = rc.parse_dctz3(blob)
    assert (hdr["height"], hdr["width"], hdr["quality"]) == (*shape,
                                                             quality)
    for a, b in zip(back, levels):
        np.testing.assert_array_equal(a, b)
    rec = rc.decode_pixels(back, quality, shape)
    assert rec.shape == (*shape, 3)
    # a faithful codec at these qualities stays close to its input
    assert np.abs(rec.astype(int) - img).mean() < 12


def test_reference_parse_rejects_a_flipped_byte():
    img = colour.colour_image("lena_like", 32, 32, seed=1)
    blob = bytearray(rc.encode_dctz3(rc.encode_levels(img, 75), 75,
                                     "exact", (32, 32)))
    blob[-1] ^= 0x01
    with pytest.raises(rc.StreamError):
        rc.parse_dctz3(bytes(blob))


def test_colour_source_never_repeats_and_keeps_the_mix():
    src = colour.ColourSource(SMALL_KODAK["images"], seed=2**31 + 5)
    k = len(SMALL_KODAK["images"])
    assert src.warm_start % k == 0
    imgs = [src.image(i) for i in range(4 * k)]
    assert [im.shape for im in imgs[:k]] == [(48, 64, 3), (48, 64, 3),
                                            (64, 48, 3)]
    digests = {im.tobytes() for im in imgs}
    assert len(digests) == len(imgs)
    again = colour.ColourSource(SMALL_KODAK["images"], seed=2**31 + 5)
    np.testing.assert_array_equal(again.image(5), imgs[5])


def _run_with(impl: str) -> dict:
    saved = control.install(control_colour.reference_engine(impl))
    try:
        return execute(small_kodak())
    finally:
        control.restore(saved)


def test_sound_program_is_correct():
    res = execute(small_kodak())
    assert res["attempted"] > 0
    assert res["correct"] is True, res["checks"]


def test_float32_reference_in_place_is_correct():
    res = _run_with("high")
    assert res["correct"] is True, res["checks"]


def test_bfloat16_reference_in_place_is_not_correct():
    res = _run_with("bf16")
    assert res["correct"] is False, res["checks"]


def _alter_stream(blob: bytes) -> bytes:
    hdr, levels = rc.parse_dctz3(blob)
    levels = [a.copy() for a in levels]
    levels[1][0, 0, 0, 1] += 1
    return rc.encode_dctz3(levels, hdr["quality"], hdr["transform"],
                           (hdr["height"], hdr["width"]))


def _half(out):
    keep = out[:max(1, len(out) // 2)]
    return [keep[i % len(keep)] for i in range(len(out))]


@pytest.mark.parametrize("fault", ["half_encode", "half_decode",
                                   "alter_level", "alter_pixel"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    from repro.serve import codec_engine as eng
    enc, dec = eng.encode_batch, eng.decode_batch

    def encode_batch(*a, **k):
        out = enc(*a, **k)
        if fault == "half_encode":
            return _half(out)
        if fault == "alter_level":
            return out[:-1] + [_alter_stream(out[-1])]
        return out

    def decode_batch(*a, **k):
        out = [np.array(r) for r in dec(*a, **k)]
        if fault == "half_decode":
            return _half(out)
        if fault == "alter_pixel":
            out[-1][0, 0, 1] ^= 0x04
        return out

    monkeypatch.setattr(eng, "encode_batch", encode_batch)
    monkeypatch.setattr(eng, "decode_batch", decode_batch)
    res = execute(small_kodak())
    assert res["attempted"] > 0
    assert res["correct"] is False, res["checks"]


def test_codec32_gives_a_batch_of_32():
    cell = run.Cell("paper512.codec.4chip")
    assert cell.chips == 4
    assert cell.traffic == dict(run.Cell("paper512.codec").traffic,
                                pixels_per_step=8388608)
    first = cell.config["images"][0]
    assert cell.traffic["pixels_per_step"] // (
        first["height"] * first["width"]) == 32
    assert cell.limits == run.Cell("paper512.codec").limits
