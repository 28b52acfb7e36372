"""Closed loop over the byte path: ``encode_batch`` then ``decode_batch``.

Each step takes the next ``batch`` distinct images of the source (one
stacked array when their shapes agree, else a list), encodes them to
``DCTZ`` streams and decodes those streams back, waiting for the
reconstructions. ``batch`` is ``pixels_per_step`` over the pixels of the
configuration's first image kind.

Warm-up runs at least ``warm_steps`` steps, and enough of them that
every base canvas of the source is encoded once, on crops the window
never uses.

Every stream and reconstruction of the window is kept and compared with
the reference once the window has closed.

Traffic keys: ``pixels_per_step``, ``transform``, ``warm_steps``.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench import checks, harness
from perfbench.images import ImageSource


class Driver:
    phases_measured = ("encode", "decode")

    def __init__(self, config: dict, traffic: dict, seed: int,
                 phases: harness.Phases):
        kinds = config["images"]
        self.quality = config["quality"]
        self.transform = traffic["transform"]
        if self.transform not in config["transforms"]:
            raise ValueError(f"{config['name']} states no transform "
                             f"{self.transform!r}")
        first = kinds[0]["height"] * kinds[0]["width"]
        self.batch = max(1, traffic["pixels_per_step"] // first)
        self.traffic = traffic
        self.seed = seed
        self.phases = phases
        self.src = ImageSource(kinds, seed)
        self.pixels = {"encode": 0, "decode": 0}
        self.n_images = 0
        self.answers = []   # (image index, stream, reconstruction)

    def _step(self, start: int, keep: bool) -> int:
        from repro.serve import codec_engine as eng
        imgs = self.src.batch(start, self.batch)
        with self.phases.phase("encode"):
            blobs = eng.encode_batch(imgs, self.quality, self.transform)
        with self.phases.phase("decode"):
            recs = harness.block(eng.decode_batch(blobs))
        px = sum(int(np.prod(im.shape)) for im in imgs)
        if keep:
            self.answers += [(start + j, blob, rec)
                             for j, (blob, rec) in enumerate(zip(blobs, recs))]
        return px

    def warm_up(self) -> None:
        steps = max(self.traffic["warm_steps"],
                    -(-self.src.n_canvases // self.batch))
        for k in range(steps):
            self._step(self.src.warm_start + k * self.batch, keep=False)

    def run_window(self, seconds: float) -> None:
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            px = self._step(self.n_images, keep=True)
            self.n_images += self.batch
            self.pixels["encode"] += px
            self.pixels["decode"] += px

    def end_to_end(self) -> dict:
        w = self.phases.wall_s
        return {"encode_mpix_s": self.pixels["encode"] / w["encode"] / 1e6,
                "decode_mpix_s": self.pixels["decode"] / w["decode"] / 1e6}

    def outcome(self) -> tuple:
        return self.n_images, 0

    def check(self, tally: checks.Tally) -> None:
        for i, blob, rec in self.answers:
            checks.check_stream(tally, self.src.image(i), blob, self.quality,
                                self.transform, rec=np.asarray(rec))
