"""Quickstart: the paper's experiment in 60 seconds.

Compresses synthetic Lena/Cable-car stand-ins with the exact DCT and the
Cordic-based Loeffler DCT, reproducing the structure of the paper's
Tables 3-4 (PSNR) and the fused-kernel codec path.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax.numpy as jnp

from repro import compile_cache
from repro.core import codec, images, metrics
from repro.kernels.fused_codec import fused_codec


def psnr_table(name, gen, sizes):
    print(f"\n=== {name}: PSNR (dB), quality=50 — paper Tables 3/4 ===")
    print(f"{'size':>12s} {'DCT':>10s} {'Cordic-Loeffler':>16s} {'gap':>6s}")
    for (h, w) in sizes:
        img = gen(h, w)
        _, p_dct = codec.roundtrip(img, 50, "exact")
        _, p_cor = codec.roundtrip(img, 50, "cordic")
        print(f"{h:>5d}x{w:<6d} {p_dct:>10.3f} {p_cor:>16.3f} "
              f"{p_dct - p_cor:>6.2f}")


def main():
    psnr_table("Lena", images.lena_like, [(200, 200), (512, 512)])
    psnr_table("Cable-car", images.cablecar_like,
               [(320, 288), (544, 512)])

    print("\n=== fused Pallas codec kernel (DCT+quant+IDCT, one pass) ===")
    img = images.lena_like(256, 256)
    rec, qc = fused_codec(img, quality=50)
    c = codec.compress(img, 50)
    print(f"PSNR: {float(metrics.psnr(jnp.asarray(img), rec)):.2f} dB | "
          f"compression ratio ~{c.compression_ratio():.1f}x | "
          f"nonzero coeffs {int((qc != 0).sum())}/{qc.size}")

    print("\n=== quality sweep (exact DCT, Lena 256x256) ===")
    for q in (10, 30, 50, 70, 90):
        _, p = codec.roundtrip(img, q, "exact")
        ratio = codec.compress(img, q).compression_ratio()
        print(f"  quality {q:3d}: {p:6.2f} dB, {ratio:5.1f}x")


if __name__ == "__main__":
    compile_cache.enable()
    main()
