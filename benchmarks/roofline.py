"""Codec-kernel roofline: achieved FLOP/s and bytes/s vs documented
peaks — thin entrypoint over ``repro.bench``.

The measurements are :func:`repro.bench.cases.roofline_points` (shared
with the ``roofline`` registry case that feeds RESULTS.md): every
routed codec kernel is timed through its public ``ops.py`` router
(tuned tiles apply when ``results/tuning.json`` is valid for this
backend) and placed on the roofline defined by the documented per-chip
peak terms of the device it runs on (:data:`repro.launch.mesh.PEAKS`,
keyed by device kind — TPU v5e: 197 TFLOP/s bf16, 819 GB/s HBM).
FLOP and byte counts come from XLA's lowered cost analysis of each
kernel's jnp reference at the same shape; the two bit-stream kernels
(``pack_bits``/``unpack_bits``) use analytic byte counts since their
FLOP content is ~0.

Off the TPU there are no peak fractions (interpret-mode Pallas timings
are no device measurement).  The ``--check-terms`` gate is therefore
timing-free: it only asserts the cost model is sane (positive byte
traffic everywhere, positive FLOPs for the arithmetic kernels, finite
intensities).

    PYTHONPATH=src python benchmarks/roofline.py
    PYTHONPATH=src python benchmarks/roofline.py --size 64 \
        --entropy-size 48 --iters 2 --check-terms
"""

from __future__ import annotations

import argparse
import sys

import jax

from benchmarks.common import rows_from_records
from repro.bench.cases import roofline_points


def check_cost_terms(records) -> list:
    """Timing-free sanity gate on the roofline cost model."""
    bad = []
    for r in records:
        m = r.metrics
        kernel = r.params["kernel"]
        if m["bytes_accessed"] <= 0:
            bad.append(f"{kernel}: no byte traffic in cost model")
        if kernel in ("dct8x8", "cordic_loeffler", "fused_codec") \
                and m["flops"] <= 0:
            bad.append(f"{kernel}: no FLOPs in cost model")
        if not (m["intensity_flop_per_byte"] >= 0):
            bad.append(f"{kernel}: non-finite arithmetic intensity")
        if m["achieved_gb_s"] <= 0:
            bad.append(f"{kernel}: non-positive achieved bandwidth")
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=256,
                    help="square image side for the image kernels")
    ap.add_argument("--entropy-size", type=int, default=128,
                    help="image side whose entropy payload drives the "
                         "bit-stream kernels")
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--check-terms", action="store_true",
                    help="exit 1 unless the cost model is sane "
                         "(positive bytes everywhere, positive FLOPs "
                         "for arithmetic kernels); never gates timings")
    args = ap.parse_args()

    print(f"# backend={jax.default_backend()} size={args.size} "
          f"entropy_size={args.entropy_size}")
    records = roofline_points(args.size, args.entropy_size,
                              warmup=args.warmup, iters=args.iters)
    rows_from_records(
        "roofline", records, legs=("routed",),
        metrics_fmt=lambda r: (
            f"gflop_s={r.metrics['achieved_gflop_s']:.3f};"
            f"gb_s={r.metrics['achieved_gb_s']:.3f};"
            f"frac_peak_flops={r.metrics.get('frac_peak_flops')};"
            f"frac_peak_bw={r.metrics.get('frac_peak_bw')};"
            f"intensity={r.metrics['intensity_flop_per_byte']:.3f}"))

    if args.check_terms:
        bad = check_cost_terms(records)
        if bad:
            print("COST-MODEL VIOLATIONS:", file=sys.stderr)
            for b in bad:
                print(f"  {b}", file=sys.stderr)
            return 1
        print("# cost-model check passed "
              f"({len(records)} kernels)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
