"""Seeded grayscale images for the benchmark's traffic.

``lena_like`` and ``cablecar_like`` are copies of the system's own
generators (the paper's Lena and Cable-car are not redistributable), kept
here so that the yardstick cannot move with the program.

:class:`ImageSource` turns a configuration's image list and a seed into a
stream of distinct images: a few base canvases per image kind, larger
than the image by ``MARGIN`` pixels, and each image a crop of one canvas
at an offset no other image of the stream uses. The canvases are the
same for every seed; the seed draws the order of the crops. So content
never repeats inside a run (no content-keyed cache of the program can
hit), every seed gets the same sizes and the same mix of content, in
another order, and a crop costs one copy on the host.
"""

from __future__ import annotations

import numpy as np

MARGIN = 64          # canvas = image + MARGIN in each axis; MARGIN**2 crops
BASES_PER_KIND = 8   # canvases per image kind
CANVAS_SEED = 1306   # the canvases, whatever the run's seed


def _grid(h: int, w: int):
    y = np.linspace(0.0, 1.0, h, endpoint=False)[:, None]
    x = np.linspace(0.0, 1.0, w, endpoint=False)[None, :]
    return y, x


def lena_like(h: int, w: int, seed: int = 0) -> np.ndarray:
    """Smooth, low-frequency-dominated grayscale image (uint8)."""
    rng = np.random.default_rng(seed)
    y, x = _grid(h, w)
    img = np.zeros((h, w), dtype=np.float64)
    for _ in range(6):
        cy, cx = rng.uniform(0.1, 0.9, size=2)
        sy, sx = rng.uniform(0.08, 0.35, size=2)
        amp = rng.uniform(-90.0, 110.0)
        img += amp * np.exp(-((y - cy) ** 2 / (2 * sy ** 2)
                              + (x - cx) ** 2 / (2 * sx ** 2)))
    img += 60.0 * (0.5 * y + 0.5 * x)
    img += 9.0 * np.sin(2 * np.pi * (7 * x + 2 * y))
    img += 6.0 * np.sin(2 * np.pi * (3 * x - 9 * y))
    img += rng.normal(0.0, 2.0, size=(h, w))
    img = img - img.min()
    img = 235.0 * img / max(img.max(), 1e-9) + 12.0
    return np.clip(img, 0, 255).astype(np.uint8)


def cablecar_like(h: int, w: int, seed: int = 1) -> np.ndarray:
    """Edge-rich grayscale image with strong high-frequency energy (uint8)."""
    rng = np.random.default_rng(seed)
    y, x = _grid(h, w)
    img = 110.0 + 70.0 * y
    for _ in range(24):
        y0, x0 = rng.uniform(0.0, 0.85, size=2)
        hh, ww = rng.uniform(0.04, 0.3, size=2)
        amp = rng.uniform(-80.0, 80.0)
        mask = ((y >= y0) & (y < y0 + hh)) * ((x >= x0) & (x < x0 + ww))
        img = img + amp * mask
    for k in range(5):
        d = np.abs((y - 0.15 - 0.12 * k) - 0.35 * x)
        img = img - 70.0 * (d < 0.004)
    img = img + 14.0 * np.sign(np.sin(2 * np.pi * (23 * x + 17 * y)))
    img = img + rng.normal(0.0, 4.0, size=(h, w))
    img = img - img.min()
    img = 243.0 * img / max(img.max(), 1e-9) + 6.0
    return np.clip(img, 0, 255).astype(np.uint8)


GENERATORS = {"lena_like": lena_like, "cablecar_like": cablecar_like}


class ImageSource:
    """Distinct images ``image(i)``, i = 0, 1, ..., from a seed.

    Image ``i`` is of kind ``i % len(kinds)``; each kind is a dict with
    ``generator``, ``height`` and ``width``. The same seed gives the same
    images, in the same order.
    """

    def __init__(self, kinds: list, seed: int):
        self.kinds = kinds
        kind_seq = np.random.SeedSequence(CANVAS_SEED)
        self._canvas = []
        for kind, ks in zip(kinds, kind_seq.spawn(len(kinds))):
            gen = GENERATORS[kind["generator"]]
            h, w = kind["height"] + MARGIN, kind["width"] + MARGIN
            seeds = ks.generate_state(BASES_PER_KIND, np.uint32)
            self._canvas.append([gen(h, w, seed=int(s)) for s in seeds])
        # one crop-offset order per (kind, base): no two images share one
        rng = np.random.default_rng(seed % (1 << 63))
        self._offsets = rng.permuted(
            np.tile(np.arange(MARGIN * MARGIN), (len(kinds),
                                                 BASES_PER_KIND, 1)),
            axis=-1)

    @property
    def capacity(self) -> int:
        return len(self.kinds) * BASES_PER_KIND * MARGIN * MARGIN

    @property
    def warm_start(self) -> int:
        """First index of the warm-up images: crops of the same canvases
        that no run reaches, so warm-up meets the window's stream sizes
        (and so its compiled shapes) without repeating its content."""
        return self.capacity // 2

    @property
    def n_canvases(self) -> int:
        return len(self.kinds) * BASES_PER_KIND

    def shape(self, i: int) -> tuple:
        kind = self.kinds[i % len(self.kinds)]
        return kind["height"], kind["width"]

    def image(self, i: int) -> np.ndarray:
        if not 0 <= i < self.capacity:
            raise IndexError(f"image {i} beyond the {self.capacity} "
                             f"distinct images of this source")
        k, rest = i % len(self.kinds), i // len(self.kinds)
        base, n = rest % BASES_PER_KIND, rest // BASES_PER_KIND
        dy, dx = divmod(int(self._offsets[k, base, n]), MARGIN)
        h, w = self.shape(i)
        return np.ascontiguousarray(
            self._canvas[k][base][dy:dy + h, dx:dx + w])

    def batch(self, start: int, n: int):
        """Images start..start+n-1: one stacked array when their shapes
        agree, else a list."""
        imgs = [self.image(i) for i in range(start, start + n)]
        if len({im.shape for im in imgs}) == 1:
            return np.stack(imgs)
        return imgs
