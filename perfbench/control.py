"""The control: the reference put in the program's place, in a lower
precision, and the program's own readings on the same seeds.

    python3 perfbench/control.py --workload thumb256.codec --seconds 3 \\
        --seeds 11 12 13 --impl program high default

``--impl program`` runs the cell as the benchmark does; ``high``,
``default`` and ``bf16`` replace ``codec_engine.encode_batch``,
``decode_batch`` and ``roundtrip_batch`` with the plain reference
(``reference.py``) computed on the device: float32 with matmuls at
``Precision.HIGH`` (three bf16 passes) or ``DEFAULT`` (one pass), or
every operation in bfloat16. Each (impl, seed) run goes through the
whole harness, window and comparison included, in this one process, and
prints one JSON line with the numbers compared. The benchmark's own runs
never run this.
"""

from __future__ import annotations

import functools
import json
import pathlib
import sys
import types

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _blocks(x):
    """(B, H, W) -> (B, H/8, W/8, 8, 8)."""
    b, h, w = x.shape
    return x.reshape(b, h // 8, 8, w // 8, 8).swapaxes(2, 3)


def _unblocks(x):
    b, gh, gw = x.shape[:3]
    return x.swapaxes(2, 3).reshape(b, gh * 8, gw * 8)


class ReferenceEngine:
    """``codec_engine``'s batch entry points, computed by the reference
    on the device in ``dtype`` with matmuls at ``precision``."""

    def __init__(self, dtype, precision):
        import jax
        import jax.numpy as jnp
        from perfbench import reference as ref
        self.jnp, self.ref = jnp, ref
        matmul = functools.partial(jnp.matmul, precision=precision)

        def fwd(imgs, quality, transform):
            x = _blocks(imgs.astype(dtype) - dtype(128.0))
            c = ref.forward(x, transform, xp=jnp, matmul=matmul)
            return jnp.round(c / jnp.asarray(ref.qtable(quality), dtype)
                             ).astype(jnp.int32)

        def inv(levels, quality, transform):
            c = levels.astype(dtype) * jnp.asarray(ref.qtable(quality), dtype)
            v = _unblocks(ref.inverse(c, transform, xp=jnp, matmul=matmul))
            return jnp.clip(jnp.round(v + dtype(128.0)), 0, 255).astype(
                jnp.uint8)

        def psnr(orig, rec):
            o, r = orig.astype(dtype), rec.astype(dtype)
            mse = jnp.mean((o - r) ** 2, axis=(1, 2))
            peak = o.max(axis=(1, 2))
            return 20.0 * jnp.log10(peak / jnp.sqrt(jnp.maximum(
                mse, dtype(1e-12))))

        static = ("quality", "transform")
        self._fwd = jax.jit(fwd, static_argnames=static)
        self._inv = jax.jit(inv, static_argnames=static)
        self._psnr = jax.jit(psnr)

    def _groups(self, imgs):
        imgs = [np.asarray(im) for im in imgs]
        groups = {}
        for i, im in enumerate(imgs):
            groups.setdefault(im.shape, []).append(i)
        return imgs, groups

    def encode_batch(self, imgs, quality=50, transform="exact", *args,
                     **kwargs):
        imgs, groups = self._groups(imgs)
        out = [None] * len(imgs)
        for (h, w), idx in groups.items():
            stack = np.stack([self.ref.pad8(imgs[i]) for i in idx])
            levels = np.asarray(self._fwd(stack, quality=quality,
                                          transform=transform))
            for j, i in enumerate(idx):
                out[i] = self.ref.encode_dctz(levels[j], quality, transform,
                                              (h, w))
        return out

    def decode_batch(self, blobs, mode="standard", *args, **kwargs):
        parsed = [self.ref.parse_dctz(b) for b in blobs]
        out = [None] * len(parsed)
        groups = {}
        for i, (hdr, lv) in enumerate(parsed):
            t = "exact" if mode == "standard" else hdr["transform"]
            groups.setdefault((lv.shape, hdr["quality"], t), []).append(i)
        for (_, quality, t), idx in groups.items():
            rec = np.asarray(self._inv(
                np.stack([parsed[i][1] for i in idx]).astype(np.int32),
                quality=quality, transform=t))
            for j, i in enumerate(idx):
                hdr = parsed[i][0]
                out[i] = rec[j, :hdr["height"], :hdr["width"]]
        return out

    def roundtrip_batch(self, imgs, quality=50, transform="exact",
                        cordic_config=None, mode="standard", with_psnr=True):
        jnp = self.jnp
        imgs = jnp.asarray(imgs)
        h, w = imgs.shape[1:]
        pad = ((0, 0), (0, (-h) % 8), (0, (-w) % 8))
        levels = self._fwd(jnp.pad(imgs, pad, mode="edge"), quality=quality,
                           transform=transform)
        inv_t = "exact" if mode == "standard" else transform
        rec = self._inv(levels, quality=quality, transform=inv_t)[:, :h, :w]
        return rec, np.asarray(self._psnr(imgs, rec), np.float64)


def reference_engine(impl: str) -> ReferenceEngine:
    import jax
    import jax.numpy as jnp
    p = jax.lax.Precision
    return {"high": lambda: ReferenceEngine(jnp.float32, p.HIGH),
            "default": lambda: ReferenceEngine(jnp.float32, p.DEFAULT),
            "bf16": lambda: ReferenceEngine(jnp.bfloat16, p.DEFAULT),
            }[impl]()


def install(engine) -> dict:
    """Put ``engine``'s entry points in ``codec_engine``'s place; returns
    the originals, for :func:`restore`."""
    from repro.serve import codec_engine as eng
    names = ("encode_batch", "decode_batch", "roundtrip_batch")
    saved = {n: getattr(eng, n) for n in names}
    for n in names:
        setattr(eng, n, getattr(engine, n))
    return saved


def restore(saved: dict) -> None:
    from repro.serve import codec_engine as eng
    for n, fn in saved.items():
        setattr(eng, n, fn)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--impl", nargs="+", default=["program"],
                    choices=("program", "high", "default", "bf16"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import run
    cell = run.Cell(args.workload)
    devices = run.open_chips(cell.chips)
    if devices is None:
        return 2
    for impl in args.impl:
        saved = None if impl == "program" else install(reference_engine(impl))
        try:
            for seed in args.seeds:
                ns = types.SimpleNamespace(seed=seed, seconds=args.seconds,
                                           trace=0)
                res = run.execute(cell, ns, devices)
                print(json.dumps({
                    "workload": cell.name, "impl": impl, "seed": seed,
                    "correct": res["correct"], "attempted": res["attempted"],
                    "checks": {k: v["value"] for k, v in
                               res["checks"].items()},
                    "metrics": {k: v["value"] for k, v in
                                res["metrics"].items()}}), flush=True)
        finally:
            if saved is not None:
                restore(saved)
    return 0


if __name__ == "__main__":
    sys.exit(main())
