"""The comparisons that decide ``correct``, against ``reference.py``.

Every number is a *gap*: how far the reference's unrounded value lies
outside the rounding interval of the integer the program produced,
``max(0, |x_ref - n_prog| - 0.5)``, maximised over the sample. A program
that computes what the reference computes, to float32 round-off, reads
about 1e-5 or less; one that rounds a value the reference puts well
inside another integer's interval reads that distance. Exact ties of
the reference (``x = k + 0.5``, frequent for DC levels) may round either
way at gap 0, which is why counts of differing integers are not used.
"""

from __future__ import annotations

import numpy as np

from perfbench import reference as ref

# A roundtrip returns pixels only, so its own levels are not seen: a
# block whose reference level lies within this many levels of a rounding
# tie may legitimately reconstruct from either neighbour and is left out
# of the pixel gap. Float32 moves a level by about 1e-5 at most.
TIE_BAND = 1e-4


def _gap(x: np.ndarray, n: np.ndarray) -> float:
    return float(np.maximum(0.0, np.abs(x - n) - 0.5).max(initial=0.0))


def unrounded_levels(img, quality: int, transform: str) -> np.ndarray:
    x = ref.to_blocks(ref.pad8(np.asarray(img)).astype(np.float64) - 128.0)
    return ref.forward(x, transform) / ref.qtable(quality)


def unrounded_pixels(levels, quality: int, transform: str) -> np.ndarray:
    x = ref.inverse(np.asarray(levels, np.float64) * ref.qtable(quality),
                    transform)
    return np.clip(ref.from_blocks(x) + 128.0, 0.0, 255.0)


class Tally:
    """Numbers compared in one run, each with its limit."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.values = {name: 0.0 for name in limits}

    def worst(self, name: str, value: float) -> None:
        self.values[name] = max(self.values[name], float(value))

    def add(self, name: str, value: float) -> None:
        self.values[name] += float(value)

    @property
    def correct(self) -> bool:
        return all(self.values[n] <= self.limits[n] for n in self.limits)

    def report(self) -> dict:
        return {n: {"value": self.values[n], "limit": self.limits[n]}
                for n in self.limits}


def check_stream(tally: Tally, img, blob: bytes, quality: int,
                 transform: str, rec=None) -> None:
    """One encoded stream (and optionally its decode) against the
    reference: parse, header, level gap, pixel gap of ``rec``."""
    try:
        hdr, levels = ref.parse_dctz(blob)
    except ref.StreamError:
        tally.add("undecodable", 1)
        return
    h, w = np.asarray(img).shape
    if (hdr["quality"], hdr["transform"], hdr["height"], hdr["width"]) != \
            (quality, transform, h, w):
        tally.add("header_mismatch", 1)
        return
    tally.worst("level_gap",
                _gap(unrounded_levels(img, quality, transform), levels))
    if rec is not None:
        v = unrounded_pixels(levels, quality, "exact")[:h, :w]
        tally.worst("pixel_gap", _gap(v, np.asarray(rec)))


def check_roundtrip(tally: Tally, img, rec, psnr: float, quality: int,
                    transform: str, mode: str) -> None:
    """One roundtrip output: pixel gap on blocks with no level near a
    rounding tie, and the gap of the PSNR it reports."""
    img = np.asarray(img)
    h, w = img.shape
    x = unrounded_levels(img, quality, transform)
    levels = np.round(x)
    inv = "exact" if mode == "standard" else transform
    v = unrounded_pixels(levels, quality, inv)
    near_tie = (np.abs(np.abs(x - levels) - 0.5) < TIE_BAND).any(
        axis=(-1, -2))
    keep = ref.from_blocks(np.broadcast_to(
        ~near_tie[..., None, None], near_tie.shape + (8, 8)))[:h, :w]
    rec = np.asarray(rec)
    if rec.shape != (h, w):
        tally.add("shape_mismatch", 1)
        return
    tally.worst("pixel_gap", _gap(v[:h, :w][keep], rec[keep]))
    # the PSNR the program reports, against eq. (23) of its own pixels
    tally.worst("psnr_gap_db", abs(float(psnr) - ref.psnr(img, rec)))
