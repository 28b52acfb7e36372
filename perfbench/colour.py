"""Seeded RGB images for the benchmark's colour traffic.

The Kodak suite's photographs are not in the repository, so a colour
image is built the way the grayscale ones are: its luminance is a
``lena_like`` (smooth) or ``cablecar_like`` (edge-rich) image of
``images.py``, and its two chroma planes are smooth seeded fields (a few
broad Gaussian blobs and a tilt each, the low-frequency colour of a
photograph), mapped to RGB by JFIF 1.02's inverse conversion and clipped.

:class:`ColourSource` keeps ``images.py``'s crop-of-canvas scheme: a few
base canvases per distinct image kind, ``MARGIN`` pixels larger than the
image, and each image a crop of one canvas at an offset no other image
of the stream uses; the seed draws the order of the crops. Kinds that
repeat in the configuration's list (the same generator and size) share
their canvases, so the list can state a mix such as Kodak's 18
landscape to 6 portrait images.
"""

from __future__ import annotations

import numpy as np

from perfbench.images import BASES_PER_KIND, CANVAS_SEED, GENERATORS, MARGIN


def _chroma_field(h: int, w: int, rng) -> np.ndarray:
    """A smooth (h, w) field of chroma offsets from 128, within +-60."""
    y = np.linspace(0.0, 1.0, h, endpoint=False)[:, None]
    x = np.linspace(0.0, 1.0, w, endpoint=False)[None, :]
    f = rng.uniform(-25.0, 25.0) * (x - 0.5) + rng.uniform(-25.0, 25.0) * (
        y - 0.5)
    for _ in range(4):
        cy, cx = rng.uniform(0.0, 1.0, size=2)
        sy, sx = rng.uniform(0.1, 0.4, size=2)
        f = f + rng.uniform(-45.0, 45.0) * np.exp(
            -((y - cy) ** 2 / (2 * sy ** 2) + (x - cx) ** 2 / (2 * sx ** 2)))
    return np.clip(f, -60.0, 60.0)


def colour_image(generator: str, h: int, w: int, seed: int) -> np.ndarray:
    """One seeded (h, w, 3) uint8 RGB image."""
    y = GENERATORS[generator](h, w, seed=seed).astype(np.float64)
    rng = np.random.default_rng([seed, 420])
    cb, cr = _chroma_field(h, w, rng), _chroma_field(h, w, rng)
    rgb = np.stack([y + 1.402 * cr, y - 0.34414 * cb - 0.71414 * cr,
                    y + 1.772 * cb], axis=-1)
    return np.clip(np.round(rgb), 0, 255).astype(np.uint8)


class ColourSource:
    """Distinct RGB images ``image(i)``, i = 0, 1, ..., from a seed.

    Image ``i`` is of kind ``i % len(kinds)``; each kind is a dict with
    ``generator``, ``height`` and ``width``. The same seed gives the same
    images, in the same order.
    """

    def __init__(self, kinds: list, seed: int):
        self.kinds = kinds
        keys = [(k["generator"], k["height"], k["width"]) for k in kinds]
        self._distinct = list(dict.fromkeys(keys))
        self._kind_of = [self._distinct.index(k) for k in keys]
        # rank of each list entry among the entries of its distinct kind
        self._rank = [self._kind_of[:i].count(d)
                      for i, d in enumerate(self._kind_of)]
        self._per_round = [self._kind_of.count(d)
                           for d in range(len(self._distinct))]
        kind_seq = np.random.SeedSequence(CANVAS_SEED + 420)
        self._canvas = []
        for (gen, h, w), ks in zip(self._distinct,
                                   kind_seq.spawn(len(self._distinct))):
            seeds = ks.generate_state(BASES_PER_KIND, np.uint32)
            self._canvas.append([colour_image(gen, h + MARGIN, w + MARGIN,
                                              int(s)) for s in seeds])
        rng = np.random.default_rng(seed % (1 << 63))
        self._offsets = rng.permuted(
            np.tile(np.arange(MARGIN * MARGIN),
                    (len(self._distinct), BASES_PER_KIND, 1)), axis=-1)

    @property
    def capacity(self) -> int:
        rounds = min(BASES_PER_KIND * MARGIN * MARGIN // n
                     for n in self._per_round)
        return rounds * len(self.kinds)

    @property
    def warm_start(self) -> int:
        """First index of the warm-up images: crops no run reaches, at the
        start of a round of the kind list, so warm-up meets the window's
        mix of sizes (and so its compiled shapes)."""
        k = len(self.kinds)
        return self.capacity // 2 // k * k

    @property
    def n_canvases(self) -> int:
        return len(self._distinct) * BASES_PER_KIND

    def shape(self, i: int) -> tuple:
        kind = self.kinds[i % len(self.kinds)]
        return kind["height"], kind["width"]

    def image(self, i: int) -> np.ndarray:
        if not 0 <= i < self.capacity:
            raise IndexError(f"image {i} beyond the {self.capacity} "
                             f"distinct images of this source")
        k, rnd = i % len(self.kinds), i // len(self.kinds)
        d = self._kind_of[k]
        occ = rnd * self._per_round[d] + self._rank[k]
        base, n = occ % BASES_PER_KIND, occ // BASES_PER_KIND
        dy, dx = divmod(int(self._offsets[d, base, n]), MARGIN)
        h, w = self.shape(i)
        return np.ascontiguousarray(
            self._canvas[d][base][dy:dy + h, dx:dx + w])

    def batch(self, start: int, n: int):
        """Images start..start+n-1: one stacked array when their shapes
        agree, else a list."""
        imgs = [self.image(i) for i in range(start, start + n)]
        if len({im.shape for im in imgs}) == 1:
            return np.stack(imgs)
        return imgs
