"""The colour control: ``reference_colour.py``'s arithmetic put in the
program's place, on the device, in a lower precision.

:class:`ColourReferenceEngine` replaces ``codec_engine.encode_batch`` and
``decode_batch`` (install it with ``control.install``): the conversion,
2x2 chroma mean, DCT and quantisation, and on decode the inverse DCT,
fancy upsampling and inverse conversion, computed with ``jax.numpy`` in
``dtype`` with matmuls at ``precision``; the streams are written and read
by the reference's own scalar coder. In float32 it passes the comparison
that decides ``correct``; in bfloat16 it fails it
(``perfbench/tests/test_colour.py``). The benchmark's runs never use it.

    python3 perfbench/control_colour.py --seconds 3 --seeds 11 12 13 \\
        --impl program high bf16
"""

from __future__ import annotations

import functools
import json
import pathlib
import sys
import types

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


class ColourReferenceEngine:
    """``codec_engine``'s colour byte path, computed by the reference on
    the device in ``dtype`` with matmuls at ``precision``."""

    def __init__(self, dtype, precision):
        import jax
        import jax.numpy as jnp

        from perfbench import reference as ref
        from perfbench import reference_colour as rc
        from perfbench.control import _blocks, _unblocks
        self.rc = rc
        matmul = functools.partial(jnp.matmul, precision=precision)

        def up(x, axis):
            n = x.shape[axis]
            prev = jnp.concatenate([jax.lax.slice_in_dim(x, 0, 1, axis=axis),
                                    jax.lax.slice_in_dim(x, 0, n - 1,
                                                         axis=axis)], axis)
            nxt = jnp.concatenate([jax.lax.slice_in_dim(x, 1, n, axis=axis),
                                   jax.lax.slice_in_dim(x, n - 1, n,
                                                        axis=axis)], axis)
            lo, hi = 0.75 * x + 0.25 * prev, 0.75 * x + 0.25 * nxt
            shape = list(x.shape)
            shape[axis] = 2 * n
            return jnp.stack([lo, hi], axis + 1).reshape(shape)

        def fwd(imgs, quality, transform):
            x = imgs.astype(dtype)
            r, g, b = x[..., 0], x[..., 1], x[..., 2]
            y = 0.299 * r + 0.587 * g + 0.114 * b - dtype(128.0)
            cb = -0.1687 * r - 0.3313 * g + 0.5 * b
            cr = 0.5 * r - 0.4187 * g - 0.0813 * b
            n, h, w = y.shape

            def mean2x2(p):
                return p.reshape(n, h // 2, 2, w // 2, 2).mean(axis=(2, 4))
            tables = rc.qtables(quality)
            out = []
            for c, p in enumerate((y, mean2x2(cb), mean2x2(cr))):
                coef = ref.forward(_blocks(p), transform, xp=jnp,
                                   matmul=matmul)
                q = jnp.asarray(tables[rc.COMPONENTS[c][2]], dtype)
                out.append(jnp.round(coef / q).astype(jnp.int32))
            return out

        def inv(levels, quality):
            tables = rc.qtables(quality)
            planes = []
            for c, lv in enumerate(levels):
                q = jnp.asarray(tables[rc.COMPONENTS[c][2]], dtype)
                planes.append(_unblocks(ref.inverse(
                    lv.astype(dtype) * q, "exact", xp=jnp, matmul=matmul)))
            y = planes[0] + dtype(128.0)
            cb, cr = (up(up(p, 1), 2) for p in planes[1:])
            rgb = jnp.stack([y + 1.402 * cr, y - 0.34414 * cb - 0.71414 * cr,
                             y + 1.772 * cb], axis=-1)
            return jnp.clip(jnp.round(rgb), 0, 255).astype(jnp.uint8)

        self._fwd = jax.jit(fwd, static_argnames=("quality", "transform"))
        self._inv = jax.jit(inv, static_argnames=("quality",))

    def encode_batch(self, imgs, quality=50, transform="exact", *args,
                     **kwargs):
        imgs = [np.asarray(im) for im in imgs]
        groups = {}
        for i, im in enumerate(imgs):
            groups.setdefault(im.shape, []).append(i)
        out = [None] * len(imgs)
        for (h, w, _), idx in groups.items():
            stack = np.stack([self.rc.pad16(imgs[i]) for i in idx])
            levels = [np.asarray(a) for a in self._fwd(
                stack, quality=quality, transform=transform)]
            for j, i in enumerate(idx):
                out[i] = self.rc.encode_dctz3([a[j] for a in levels],
                                              quality, transform, (h, w))
        return out

    def roundtrip_batch(self, *args, **kwargs):
        raise NotImplementedError("the colour cell has no roundtrip")

    def decode_batch(self, blobs, *args, **kwargs):
        parsed = [self.rc.parse_dctz3(b) for b in blobs]
        groups = {}
        for i, (hdr, lv) in enumerate(parsed):
            groups.setdefault((lv[0].shape, hdr["quality"]), []).append(i)
        out = [None] * len(parsed)
        for (_, quality), idx in groups.items():
            stacks = [np.stack([parsed[i][1][c] for i in idx]).astype(
                np.int32) for c in range(3)]
            rec = np.asarray(self._inv(stacks, quality=quality))
            for j, i in enumerate(idx):
                hdr = parsed[i][0]
                out[i] = rec[j, :hdr["height"], :hdr["width"]]
        return out


def reference_engine(impl: str) -> ColourReferenceEngine:
    import jax
    import jax.numpy as jnp
    p = jax.lax.Precision
    return {"high": lambda: ColourReferenceEngine(jnp.float32, p.HIGH),
            "default": lambda: ColourReferenceEngine(jnp.float32, p.DEFAULT),
            "bf16": lambda: ColourReferenceEngine(jnp.bfloat16, p.DEFAULT),
            }[impl]()


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="kodak420.codec")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--impl", nargs="+", default=["program"],
                    choices=("program", "high", "default", "bf16"))
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from perfbench import control, run
    cell = run.Cell(args.workload)
    devices = run.open_chips(cell.chips)
    if devices is None:
        return 2
    for impl in args.impl:
        saved = (None if impl == "program"
                 else control.install(reference_engine(impl)))
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
                control.restore(saved)
    return 0


if __name__ == "__main__":
    sys.exit(main())
