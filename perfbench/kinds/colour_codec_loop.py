"""Closed loop over the colour byte path: ``encode_batch`` then
``decode_batch`` on (H, W, 3) RGB images.

Each step takes the next ``batch`` distinct images of a
:class:`perfbench.colour.ColourSource` (one stacked array when their
shapes agree, else a list), encodes them to ``DCTZ`` streams and decodes
those streams back, waiting for the reconstructions. ``batch`` is
``pixels_per_step`` over the pixels of the configuration's first image
kind; a pixel is one RGB pixel.

Warm-up runs at least ``warm_steps`` steps, and enough of them that
every base canvas is encoded once, on crops the window never uses. The
first warm-up decode must come back as (H, W, 3) images: a program that
codes no colour fails there, at once.

Every stream and reconstruction of the window is kept and compared, once
the window has closed, with ``reference_colour.py``: the stream must
parse as a colour stream of the image's shape, quality and transform;
the level gap is the worst over the three components; the pixel gap is
the worst over the three channels of the reconstruction.

Traffic keys: ``pixels_per_step``, ``transform``, ``warm_steps``.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench import checks, harness
from perfbench import reference_colour as rc
from perfbench.colour import ColourSource


def _gap(x: np.ndarray, n: np.ndarray) -> float:
    return float(np.maximum(0.0, np.abs(x - n) - 0.5).max(initial=0.0))


def check_stream(tally: checks.Tally, img, blob: bytes, quality: int,
                 transform: str, rec=None) -> None:
    """One colour stream (and optionally its decode) against the
    reference: parse, header, level gap, pixel gap and shape of ``rec``."""
    try:
        hdr, levels = rc.parse_dctz3(blob)
    except rc.StreamError:
        tally.add("undecodable", 1)
        return
    h, w = np.asarray(img).shape[:2]
    if (hdr["quality"], hdr["transform"], hdr["height"], hdr["width"]) != \
            (quality, transform, h, w):
        tally.add("header_mismatch", 1)
        return
    want = rc.unrounded_levels(img, quality, transform)
    tally.worst("level_gap", max(_gap(x, n) for x, n in zip(want, levels)))
    if rec is None:
        return
    rec = np.asarray(rec)
    if rec.shape != (h, w, 3):
        tally.add("shape_mismatch", 1)
        return
    v = rc.unrounded_rgb(levels, quality)[:h, :w]
    tally.worst("pixel_gap", _gap(v, rec))


class Driver:
    phases_measured = ("encode", "decode")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 phases: harness.Phases):
        kinds = config["images"]
        self.quality = config["quality"]
        self.tables = config.get("tables", "auto")
        self.transform = traffic["transform"]
        if self.transform not in config["transforms"]:
            raise ValueError(f"{config['name']} states no transform "
                             f"{self.transform!r}")
        first = kinds[0]["height"] * kinds[0]["width"]
        self.batch = max(1, traffic["pixels_per_step"] // first)
        self.traffic = traffic
        self.phases = phases
        self.src = ColourSource(kinds, seed)
        self.pixels = {"encode": 0, "decode": 0}
        self.n_images = 0
        self.answers = []   # (image index, stream, reconstruction)
        self._counts = ({}, {})

    def _step(self, start: int, keep: bool) -> tuple:
        from repro.serve import codec_engine as eng
        imgs = self.src.batch(start, self.batch)
        with self.phases.phase("encode"):
            blobs = eng.encode_batch(imgs, self.quality, self.transform,
                                     tables=self.tables)
        with self.phases.phase("decode"):
            recs = harness.block(eng.decode_batch(blobs))
        px = sum(im.shape[0] * im.shape[1] for im in imgs)
        if keep:
            self.answers += [(start + j, blob, rec)
                             for j, (blob, rec) in enumerate(zip(blobs, recs))]
        return px, recs

    def warm_up(self) -> None:
        steps = max(self.traffic["warm_steps"],
                    -(-self.src.n_canvases // self.batch))
        for k in range(steps):
            start = self.src.warm_start + k * self.batch
            _, recs = self._step(start, keep=False)
            if k == 0:
                want = self.src.shape(start) + (3,)
                got = tuple(np.shape(recs[0]))
                if got != want:
                    raise RuntimeError(
                        f"the first warm-up decode came back {got}, not "
                        f"{want}: the program does not code colour")

    def run_window(self, seconds: float) -> None:
        from repro import obs
        before = obs.counts()
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            px, _ = self._step(self.n_images, keep=True)
            self.n_images += self.batch
            self.pixels["encode"] += px
            self.pixels["decode"] += px
        self._counts = (before, obs.counts())

    def counters(self) -> dict:
        """The program's counters over the window (``repro.obs``)."""
        before, after = self._counts
        return {k: v - before.get(k, 0) for k, v in after.items()
                if v != before.get(k, 0)}

    def end_to_end(self) -> dict:
        w = self.phases.wall_s
        return {"encode_mpix_s": self.pixels["encode"] / w["encode"] / 1e6,
                "decode_mpix_s": self.pixels["decode"] / w["decode"] / 1e6}

    def outcome(self) -> tuple:
        return self.n_images, 0

    def check(self, tally: checks.Tally) -> None:
        for i, blob, rec in self.answers:
            check_stream(tally, self.src.image(i), blob, self.quality,
                         self.transform, rec=rec)
