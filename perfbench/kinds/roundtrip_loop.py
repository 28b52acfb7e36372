"""Closed loop over ``roundtrip_batch`` on device-resident batches.

Set-up uploads ``batches_per_combo`` stacked batches of ``batch``
distinct images for every combination of the configuration's image
kinds and the transforms this mix names (with the decode mode it gives
each), as a quality-evaluation pipeline whose images already live on the
device. The window cycles through those batches, combination by
combination, and waits for each reconstruction and its PSNR. The fused
roundtrip keeps no content-keyed state, so cycling a pool cannot flatter
it. The comparison takes ``sample`` calls of every combination, drawn
from the seed, and checks every image of them.

Traffic keys: ``batch``, ``modes`` (transform -> decode mode),
``batches_per_combo``, ``warm_rounds``, ``sample``.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench import checks, harness
from perfbench.images import ImageSource


class Driver:
    phases_measured = ("roundtrip",)

    def __init__(self, config: dict, traffic: dict, seed: int,
                 phases: harness.Phases):
        import jax
        self.quality = config["quality"]
        self.phases = phases
        self.batch = traffic["batch"]
        self.traffic = traffic
        for t in traffic["modes"]:
            if t not in config["transforms"]:
                raise ValueError(f"{config['name']} states no transform "
                                 f"{t!r}")
        kinds = config["images"]
        self.combos = [(k, t, m) for k in range(len(kinds))
                       for t, m in traffic["modes"].items()]
        per = traffic["batches_per_combo"]
        self.sources = [ImageSource([kind], seed + k)
                        for k, kind in enumerate(kinds)]
        # pool[c][b]: host images (for the reference) and device batch
        self.starts = [[(c * per + b) * self.batch for b in range(per)]
                       for c in range(len(self.combos))]
        self.pool = [[jax.device_put(self.sources[k].batch(s, self.batch))
                      for s in self.starts[c]]
                     for c, (k, _, _) in enumerate(self.combos)]
        self.warm_pool = [jax.device_put(self.sources[k].batch(
            self.sources[k].warm_start, self.batch))
            for k, _, _ in self.combos]
        harness.block((self.pool, self.warm_pool))
        self.pixels = {"roundtrip": 0}
        self.calls = 0
        self.samples = [harness.Reservoir(traffic["sample"], seed + c)
                        for c in range(len(self.combos))]

    def _call(self, c: int, imgs):
        from repro.serve import codec_engine as eng
        _, transform, mode = self.combos[c]
        with self.phases.phase("roundtrip"):
            rec, psnr = eng.roundtrip_batch(imgs, self.quality, transform,
                                            mode=mode)
            harness.block(rec)
        return rec, psnr

    def warm_up(self) -> None:
        for _ in range(self.traffic["warm_rounds"]):
            for c, imgs in enumerate(self.warm_pool):
                self._call(c, imgs)

    def run_window(self, seconds: float) -> None:
        per = self.traffic["batches_per_combo"]
        n = len(self.combos)
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            c, b = self.calls % n, (self.calls // n) % per
            imgs = self.pool[c][b]
            rec, psnr = self._call(c, imgs)
            self.calls += 1
            self.pixels["roundtrip"] += int(np.prod(imgs.shape))
            self.samples[c].offer(lambda: (c, b, rec, psnr))

    def end_to_end(self) -> dict:
        return {"roundtrip_mpix_s": self.pixels["roundtrip"]
                / self.phases.wall_s["roundtrip"] / 1e6}

    def outcome(self) -> tuple:
        return self.calls * self.batch, 0

    def work_calls(self) -> list:
        """(shape of one call's batch, calls) for the roofline's work."""
        shapes = {}
        n, per = len(self.combos), self.traffic["batches_per_combo"]
        for i in range(self.calls):
            shape = tuple(self.pool[i % n][(i // n) % per].shape)
            shapes[shape] = shapes.get(shape, 0) + 1
        return list(shapes.items())

    def check(self, tally: checks.Tally) -> None:
        for c, b, rec, psnr in (item for s in self.samples
                                for item in s.items):
            k, transform, mode = self.combos[c]
            rec = np.asarray(rec)
            if rec.shape[0] != self.batch:
                tally.add("shape_mismatch", 1)
                continue
            for j in range(self.batch):
                img = self.sources[k].image(self.starts[c][b] + j)
                checks.check_roundtrip(tally, img, rec[j], psnr[j],
                                       self.quality, transform, mode)
