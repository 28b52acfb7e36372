"""Entropy-stage throughput: vectorized vs scalar-reference coding —
thin entrypoint over ``repro.bench``.

The measurements are :func:`repro.bench.cases.entropy_throughput_points`
(shared with the ``entropy_throughput`` registry case that feeds
RESULTS.md); this script keeps a CSV interface and the
``--check-identical`` CI gate, which covers the whole entropy stage:
the vectorized encoder/decoder must produce byte-identical output to
the scalar reference path; every routed pack-bits backend (the staged
NumPy reference and the Pallas scatter-pack kernel, interpret mode
off-TPU) must produce byte-identical payloads and whole ``DCTZ``
streams; every routed unpack-bits backend (the staged speculative
NumPy decode and the Pallas speculative kernel, interpret mode off-TPU)
must decode coefficients identical to ``decode_payload_reference`` and
reject truncated streams with the LUT walk's exact errors; and every
symbolize route (the host symbolizer and the Pallas symbolize kernel,
interpret mode off-TPU) must match the scalar
``symbolize_reference`` oracle element-for-element — streams,
histograms, payload bytes, RangeError messages, and whole framed
``DCTZ`` v1/v2 containers under every table policy — all on random
*and* adversarial blocks (max-magnitude amplitudes, all-zero blocks,
ZRL chains).  Speed numbers are reported but never gated —
shared CI runners are too noisy for timing asserts
(docs/benchmarks.md).

    PYTHONPATH=src python benchmarks/bench_entropy_throughput.py
    PYTHONPATH=src python benchmarks/bench_entropy_throughput.py \
        --size 128 --batches 1 4 --check-identical
"""

from __future__ import annotations

import argparse
import sys

import jax

from repro.bench.cases import (entropy_identity_violations,
                               entropy_throughput_points,
                               packing_identity_violations,
                               symbolize_identity_violations,
                               unpack_identity_violations)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=256,
                    help="square image side for the throughput sweep")
    ap.add_argument("--batches", type=int, nargs="+", default=[1, 4, 8])
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--trials", type=int, default=25,
                    help="random batches for --check-identical")
    ap.add_argument("--check-identical", action="store_true",
                    help="exit 1 unless the vectorized entropy path is "
                         "byte-identical to the scalar reference AND "
                         "every routed pack-bits backend (staged NumPy "
                         "+ Pallas kernel) is byte-identical to the "
                         "NumPy reference AND every routed unpack-bits "
                         "backend decodes (and rejects malformed "
                         "streams) identically to the scalar decode "
                         "oracle AND every symbolize route "
                         "(host symbolizer + Pallas kernel) matches "
                         "the scalar symbolize oracle — streams, "
                         "histograms, payloads and framed DCTZ v1/v2 "
                         "containers — on random + adversarial blocks")
    args = ap.parse_args()

    print(f"# backend={jax.default_backend()} "
          f"devices={jax.local_device_count()} size={args.size}")

    if args.check_identical:
        bad = (entropy_identity_violations(trials=args.trials)
               + packing_identity_violations(trials=args.trials)
               + unpack_identity_violations(trials=args.trials)
               + symbolize_identity_violations(trials=args.trials))
        if bad:
            print("IDENTITY VIOLATIONS:", file=sys.stderr)
            for line in bad:
                print(f"  {line}", file=sys.stderr)
            return 1
        print(f"identity OK: vectorized == reference, routed packing "
              f"backends == NumPy reference, routed unpack backends "
              f"== scalar decode oracle, and routed symbolize "
              f"backends == scalar symbolize oracle on {args.trials} "
              f"random cases + adversarial blocks")

    records = entropy_throughput_points(args.size, sorted(args.batches),
                                        warmup=1, iters=args.iters)
    stage = records[0]
    print(f"entropy stage {args.size}x{args.size}: "
          f"encode {stage.metrics['enc_speedup']:.1f}x "
          f"({stage.metrics['enc_mb_per_s']:.1f} MB/s), "
          f"decode {stage.metrics['dec_speedup']:.1f}x "
          f"({stage.metrics['dec_mb_per_s']:.1f} MB/s) vs reference")
    for r in records:
        if not r.label.startswith("encode_stages"):
            continue
        us = {k: v["median_us"] for k, v in r.timings_us.items()}
        print(f"encode stages {args.size}x{args.size}: "
              f"symbolize {us['stage_symbolize']:.0f}us, "
              f"tables {us['stage_table_choice']:.0f}us, "
              f"codeword {us['stage_codeword']:.0f}us, "
              f"pack {us['stage_pack']:.0f}us; "
              f"transfer {r.metrics['device_transfer_bytes_per_image']:.0f}B"
              f" device vs {r.metrics['host_transfer_bytes_per_image']:.0f}B"
              f" host ({r.metrics['transfer_reduction']:.1f}x)")
    print("batch,enc_img_per_s,dec_img_per_s,enc_mb_per_s,"
          "speedup_vs_reference")
    for r in records:
        if "batch" not in r.params:
            continue
        print(f"{r.params['batch']},{r.metrics['enc_img_per_s']:.2f},"
              f"{r.metrics['dec_img_per_s']:.2f},"
              f"{r.metrics['enc_mb_per_s']:.2f},"
              f"{r.metrics['speedup_vs_reference']:.2f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
