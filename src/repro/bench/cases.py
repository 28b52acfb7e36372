"""Registered benchmark cases: the paper's four tables + serving benches.

Every case is declarative about *what* it measures (image family, size
grid, transform, quality) and delegates *how* to the shared machinery:
:func:`repro.bench.timer.measure` for timing and
:mod:`repro.serve.codec_engine` for the accelerated leg, so CPU-vs-
accelerated comparisons run one code path (the engine routes to the
fused Pallas kernel on TPU and to the bit-exact staged path elsewhere).

Legs for the timing tables (paper Tables 1-2):

* ``serial``   — the paper's CPU code shape: ``lax.map`` over 8x8 blocks,
  one at a time, unfused three-pass DCT/quant/IDCT,
* ``parallel`` — the serving path: :func:`codec_engine.roundtrip_batch`
  on a batch of one (all blocks batched; fused kernel on TPU).

This container has no GPU, so the paper's CPU-vs-GTX480 contrast is
reproduced structurally on whatever backend jax reports; the *trend with
image size* and the serial/parallel ratio are the reproduction targets,
not GTX-480 milliseconds (see PAPER.md and docs/benchmarks.md).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.registry import RunContext, benchmark
from repro.bench.schema import BenchRecord
from repro.bench.timer import measure
from repro.core import codec, dct, images, metrics, quant

QUALITY = 50               # the paper's fixed JPEG quality factor

# Size grids per suite.  "smoke" = smallest point (CI / tests), "paper" =
# the representative subset, "full" = the paper's complete grid.
TABLE1_GRID = {
    "smoke": [(200, 200)],
    "paper": [(1024, 1024), (512, 512), (200, 200)],
    "full": list(images.LENA_SIZES),
}
TABLE2_GRID = {
    "smoke": [(320, 288)],
    "paper": list(images.CABLECAR_SIZES[:3]),
    "full": list(images.CABLECAR_SIZES),
}
TABLE3_GRID = {
    "smoke": [(200, 200)],
    "paper": [(200, 200), (512, 512)],
    "full": [(200, 200), (512, 512), (2048, 2048), (3072, 3072)],
}
TABLE4_GRID = {
    "smoke": [(320, 288)],
    "paper": [(320, 288), (384, 352)],
    "full": list(reversed(images.CABLECAR_SIZES)),
}
BATCH_GRID = {"smoke": 8, "paper": 64, "full": 256}


def batch_sizes(max_batch: int) -> list:
    """The power-of-two batch grid shared by the registry case and the
    CI monotone gate (``benchmarks/bench_batch_throughput.py``)."""
    return [b for b in (1, 2, 4, 8, 16, 32, 64, 128, 256) if b <= max_batch]


def _grid(table: dict, suite: str) -> list:
    return table.get(suite, table["paper"])


# ---------------------------------------------------------------------------
# Legs
# ---------------------------------------------------------------------------

@jax.jit
def _serial_codec(img, q):
    """The paper's CPU loop shape: per-block sequential three-pass codec."""
    x = img.astype(jnp.float32) - 128.0
    blocks = dct.to_blocks(x)
    hb, wb = blocks.shape[0], blocks.shape[1]
    flat = blocks.reshape(hb * wb, 8, 8)

    def one(block):
        coef = dct.dct2d(block)
        qc = jnp.round(coef / q)
        return dct.idct2d(qc * q)

    out = jax.lax.map(one, flat)   # sequential over blocks
    rec = dct.from_blocks(out.reshape(hb, wb, 8, 8))
    return jnp.clip(jnp.round(rec + 128.0), 0, 255).astype(jnp.uint8)


def _parallel_roundtrip(img: jnp.ndarray):
    """The serving path on a batch of one (fused on TPU, staged on CPU)."""
    from repro.serve import codec_engine
    rec, _ = codec_engine.roundtrip_batch(img[None], QUALITY, "exact",
                                          with_psnr=False)
    return rec


def _timing_records(sizes, image_fn, family: str, ctx: RunContext) -> list:
    q = quant.qtable(QUALITY)
    timer = ctx.timer.scaled(warmup=max(ctx.timer.warmup, 1))
    records = []
    for (h, w) in sizes:
        img = jnp.asarray(image_fn(h, w))
        t_par = measure(_parallel_roundtrip, img,
                        warmup=timer.warmup, iters=timer.iters)
        # the engine pads internally; the serial leg needs the same
        # 8-multiple padding (the paper's 1024x814 is not block-aligned)
        t_ser = measure(_serial_codec, codec.pad_to_block(img), q,
                        warmup=timer.warmup, iters=timer.iters)
        records.append(BenchRecord(
            label=f"{family}_{h}x{w}",
            params={"height": h, "width": w, "image": family,
                    "transform": "exact", "quality": QUALITY},
            timings_us={"parallel": t_par.to_json(),
                        "serial": t_ser.to_json()},
            metrics={"speedup": t_ser.median_us / t_par.median_us,
                     "mpix_per_s": (h * w) / t_par.median_us}))
    return records


def _psnr_records(sizes, image_fn, family: str) -> list:
    records = []
    for (h, w) in sizes:
        img = image_fn(h, w)
        _, p_dct = codec.roundtrip(img, QUALITY, "exact")
        _, p_cor = codec.roundtrip(img, QUALITY, "cordic")
        records.append(BenchRecord(
            label=f"{family}_{h}x{w}",
            params={"height": h, "width": w, "image": family,
                    "quality": QUALITY},
            metrics={"psnr_db_exact": p_dct, "psnr_db_cordic": p_cor,
                     "gap_db": p_dct - p_cor}))
    return records


# ---------------------------------------------------------------------------
# Paper tables
# ---------------------------------------------------------------------------

@benchmark("table1_lena", suites=("smoke", "paper", "full"), table="Table 1",
           description="DCT codec time vs Lena size, serial vs parallel leg")
def table1_lena(ctx: RunContext) -> list:
    return _timing_records(_grid(TABLE1_GRID, ctx.suite),
                           images.lena_like, "lena", ctx)


@benchmark("table2_cablecar", suites=("smoke", "paper", "full"),
           table="Table 2",
           description="DCT codec time vs Cable-car size, serial vs parallel")
def table2_cablecar(ctx: RunContext) -> list:
    return _timing_records(_grid(TABLE2_GRID, ctx.suite),
                           images.cablecar_like, "cablecar", ctx)


@benchmark("table3_psnr_lena", suites=("smoke", "paper", "full"),
           table="Table 3",
           description="PSNR of exact DCT vs Cordic-Loeffler DCT on Lena")
def table3_psnr_lena(ctx: RunContext) -> list:
    return _psnr_records(_grid(TABLE3_GRID, ctx.suite),
                         images.lena_like, "lena")


@benchmark("table4_psnr_cablecar", suites=("smoke", "paper", "full"),
           table="Table 4",
           description="PSNR of exact DCT vs Cordic-Loeffler on Cable-car")
def table4_psnr_cablecar(ctx: RunContext) -> list:
    return _psnr_records(_grid(TABLE4_GRID, ctx.suite),
                         images.cablecar_like, "cablecar")


# ---------------------------------------------------------------------------
# Rate–distortion (measured bytes through the entropy stage)
# ---------------------------------------------------------------------------

RD_QUALITIES = {
    "smoke": [10, 50, 90],
    "paper": [10, 30, 50, 70, 90],
    "full": [10, 20, 30, 40, 50, 60, 70, 80, 90],
}
RD_IMAGES = {
    "smoke": [("lena", images.lena_like, (200, 200))],
    "paper": [("lena", images.lena_like, (512, 512)),
              ("cablecar", images.cablecar_like, (320, 288))],
}
RD_IMAGES["full"] = RD_IMAGES["paper"]


def rate_distortion_points(image_fn, family: str, h: int, w: int,
                           qualities, warmup: int, iters: int) -> list:
    """Measured rate–distortion sweep for one image: one record per
    quality with real container bytes, PSNR, and encode/decode timings.

    Shared by the ``rate_distortion`` registry case and the
    ``benchmarks/bench_rate_distortion.py`` CI gate.

    Args:
        image_fn: (h, w) -> uint8 image generator.
        family: label prefix ("lena"/"cablecar").
        h, w: image size.
        qualities: JPEG quality factors to sweep.
        warmup: untimed leading calls per leg (compile + cache warm).
        iters: timed calls per leg.

    Returns:
        BenchRecord list; ``metrics["bpp"]`` is *measured*
        bits-per-pixel (``8 * len(stream) / (h * w)``), not the
        ``estimate_bits`` proxy.
    """
    from repro.core import entropy
    img = image_fn(h, w)
    records = []
    for q in qualities:
        blob = entropy.encode_image(img, q)
        rec = entropy.decode_image(blob)
        psnr = float(metrics.psnr(jnp.asarray(img), rec))
        t_enc = measure(entropy.encode_image, img, q,
                        warmup=warmup, iters=iters)
        t_dec = measure(entropy.decode_image, blob,
                        warmup=warmup, iters=iters)
        bpp = len(blob) * 8 / (h * w)
        records.append(BenchRecord(
            label=f"{family}_{h}x{w}_q{q}",
            params={"height": h, "width": w, "image": family,
                    "quality": q, "transform": "exact",
                    "nbytes": len(blob)},
            timings_us={"encode": t_enc.to_json(),
                        "decode": t_dec.to_json()},
            metrics={"bpp": bpp, "compression_ratio": 8.0 / bpp,
                     "psnr_db": psnr,
                     "enc_mpix_per_s": (h * w) / t_enc.median_us,
                     "dec_mpix_per_s": (h * w) / t_dec.median_us}))
    return records


def check_rd_monotone(points) -> list:
    """Rate–distortion monotonicity violations over (quality, bpp, psnr).

    Higher quality must cost more measured bits-per-pixel and buy more
    PSNR; that joint ordering is the CI gate for the entropy stage.

    Args:
        points: iterable of (quality, bpp, psnr_db) tuples (any order;
            duplicate qualities collapse to one point — re-measuring
            the same quality is not a violation).

    Returns:
        ``(metric_name, lower_quality, higher_quality)`` tuples where
        the metric failed to strictly increase with quality.
    """
    pts = sorted({q: (q, b, p) for q, b, p in sorted(points)}.values())
    bad = []
    for (q1, b1, p1), (q2, b2, p2) in zip(pts, pts[1:]):
        if b2 <= b1:
            bad.append(("bpp", q1, q2))
        if p2 <= p1:
            bad.append(("psnr", q1, q2))
    return bad


@benchmark("rate_distortion", suites=("smoke", "paper", "full"),
           description="measured bits-per-pixel, PSNR and encode/decode "
                       "throughput vs quality (entropy-coded bytes)")
def rate_distortion(ctx: RunContext) -> list:
    """Quality sweep through the full codec: DCT -> quantise -> zig-zag
    -> RLE -> canonical Huffman -> ``DCTZ`` container, sizes measured
    from the real stream."""
    qualities = RD_QUALITIES.get(ctx.suite, RD_QUALITIES["paper"])
    grid = RD_IMAGES.get(ctx.suite, RD_IMAGES["paper"])
    timer = ctx.timer.scaled(warmup=max(ctx.timer.warmup, 1))
    records = []
    for family, image_fn, (h, w) in grid:
        records.extend(rate_distortion_points(
            image_fn, family, h, w, qualities,
            warmup=timer.warmup, iters=timer.iters))
    return records


# ---------------------------------------------------------------------------
# Entropy throughput (vectorized host coding vs the scalar reference)
# ---------------------------------------------------------------------------

ENTROPY_GRID = {
    "smoke": {"size": 128, "batches": [1, 4]},
    "paper": {"size": 256, "batches": [1, 2, 4, 8]},
    "full": {"size": 512, "batches": [1, 2, 4, 8, 16]},
}


def _entropy_stage_inputs(size: int, quality: int = QUALITY):
    """(z, dc_diff, ac, payload, tables, n_blocks) for one image's
    entropy-stage legs, derived once outside the timed region."""
    from repro.core.entropy import dense, huffman, scan
    img = images.lena_like(size, size)
    c = codec.compress(img, quality)
    z = np.asarray(scan.block_stream(jnp.asarray(c.qcoeffs)))
    dc_diff = np.diff(z[:, 0].astype(np.int64), prepend=np.int64(0))
    ac = z[:, 1:].astype(np.int64)
    d = dense.symbolize_dense(dc_diff, ac)
    dc_t, ac_t = huffman.build_table(d.dc_freq), huffman.build_table(d.ac_freq)
    payload = dense.encode_payload_dense(d, dc_t, ac_t)
    return z, dc_diff, ac, payload, (dc_t, ac_t), z.shape[0]


def reference_encode_stream(dc_diff, ac) -> bytes:
    """The PR 3 scalar host path: per-block symbolisation + uncached
    tables + packing.  The golden baseline the vectorized legs are
    measured (and identity-checked) against."""
    from repro.core.entropy import huffman, rle
    syms = rle.symbolize_reference(dc_diff, ac)
    dc_freq, ac_freq = rle.symbol_frequencies(syms[0], syms[1])
    return rle.encode_payload(*syms, huffman.build_table(dc_freq),
                              huffman.build_table(ac_freq))


def vectorized_encode_stream(dc_diff, ac) -> bytes:
    """The production host path over the same inputs (the dense
    symbolizer, uncached tables for a fair comparison)."""
    from repro.core.entropy import dense, huffman
    d = dense.symbolize_dense(dc_diff, ac)
    return dense.encode_payload_dense(d, huffman.build_table(d.dc_freq),
                                      huffman.build_table(d.ac_freq))


def entropy_throughput_points(size: int, batches, warmup: int,
                              iters: int) -> list:
    """Measured records for the ``entropy_throughput`` case.

    One ``entropy_stage`` record times the host entropy stage in
    isolation on a single image — vectorized vs scalar-reference, both
    directions — and one ``encode_batch_{b}`` / ``decode_batch_{b}``
    record per batch size drives the engine's overlapped byte path,
    scoring ``speedup_vs_reference`` against the
    single-image reference end-to-end rate (device compress + scalar
    host coding), the PR 3 code shape.

    Shared by the registry case and
    ``benchmarks/bench_entropy_throughput.py``.
    """
    from repro.core.entropy import rle
    from repro.serve import codec_engine

    (z, dc_diff, ac, payload, (dc_t, ac_t),
     n_blocks) = _entropy_stage_inputs(size)
    mb = size * size / 1e6          # decoded image payload in MB
    shape = (size, size)

    t_enc_vec = measure(vectorized_encode_stream, dc_diff, ac,
                        warmup=warmup, iters=iters)
    t_enc_ref = measure(reference_encode_stream, dc_diff, ac,
                        warmup=min(warmup, 1), iters=max(iters // 2, 2))
    t_dec_vec = measure(rle.decode_payload, payload, n_blocks, dc_t, ac_t,
                        warmup=warmup, iters=iters)
    t_dec_ref = measure(rle.decode_payload_reference, payload, n_blocks,
                        dc_t, ac_t,
                        warmup=min(warmup, 1), iters=max(iters // 2, 2))
    records = [BenchRecord(
        label=f"entropy_stage_{size}",
        params={"height": size, "width": size, "image": "lena",
                "quality": QUALITY, "n_blocks": n_blocks,
                "payload_nbytes": len(payload)},
        timings_us={"enc_vectorized": t_enc_vec.to_json(),
                    "enc_reference": t_enc_ref.to_json(),
                    "dec_vectorized": t_dec_vec.to_json(),
                    "dec_reference": t_dec_ref.to_json()},
        metrics={"enc_speedup": t_enc_ref.median_us / t_enc_vec.median_us,
                 "dec_speedup": t_dec_ref.median_us / t_dec_vec.median_us,
                 "enc_mb_per_s": mb / (t_enc_vec.median_us / 1e6),
                 "dec_mb_per_s": mb / (t_dec_vec.median_us / 1e6)})]

    # per-stage encode breakdown: the host symbolizer split into its
    # stages (symbolize incl. histograms / table choice / codeword
    # lookup / bit pack), plus the host<->device traffic each symbolize
    # routing implies (docs/benchmarks.md)
    from repro.core.entropy import bitio, dense, huffman

    d = dense.symbolize_dense(dc_diff, ac)
    fields, widths = dense.encode_fields_dense(d, dc_t, ac_t)
    t_sym = measure(dense.symbolize_dense, dc_diff, ac,
                    warmup=warmup, iters=iters)
    t_tab = measure(lambda: (huffman.build_table(d.dc_freq),
                             huffman.build_table(d.ac_freq)),
                    warmup=warmup, iters=iters)
    t_cw = measure(dense.encode_fields_dense, d, dc_t, ac_t,
                   warmup=warmup, iters=iters)
    t_pack = measure(bitio.pack_bits, fields, widths,
                     warmup=warmup, iters=iters)
    # host-routed encode pulls the full int32 coefficient tensor; the
    # device-resident chain pulls two (1, 256) int32 histograms, one
    # scalar bit count + flag, and the finished payload bytes
    host_xfer = n_blocks * 64 * 4
    device_xfer = 2 * 256 * 4 + 8 + len(payload)
    records.append(BenchRecord(
        label=f"encode_stages_{size}",
        params={"height": size, "width": size, "image": "lena",
                "quality": QUALITY, "n_blocks": n_blocks,
                "payload_nbytes": len(payload)},
        timings_us={"stage_symbolize": t_sym.to_json(),
                    "stage_table_choice": t_tab.to_json(),
                    "stage_codeword": t_cw.to_json(),
                    "stage_pack": t_pack.to_json()},
        metrics={
            "host_transfer_bytes_per_image": float(host_xfer),
            "device_transfer_bytes_per_image": float(device_xfer),
            "transfer_reduction": host_xfer / device_xfer,
        }))

    # single-image reference end-to-end rate: sharded device compress
    # (shared by both code shapes) + the scalar host coding PR 3 paid
    img1 = images.lena_like(size, size, seed=0)[None]

    def ref_encode_e2e():
        cb = codec_engine.compress_batch(img1, QUALITY)
        jax.device_get(cb.groups[0].qcoeffs)    # the device->host copy
        return reference_encode_stream(dc_diff, ac)

    t_ref_e2e = measure(ref_encode_e2e, warmup=min(warmup, 1),
                        iters=max(iters // 2, 2))
    ref_img_per_s = 1e6 / t_ref_e2e.median_us

    for b in batches:
        imgs = np.stack([images.lena_like(size, size, seed=i)
                         for i in range(b)])

        t_enc = measure(codec_engine.encode_batch, imgs, QUALITY,
                        warmup=warmup, iters=iters)
        blobs = codec_engine.encode_batch(imgs, QUALITY)
        nbytes = sum(len(x) for x in blobs)
        t_dec = measure(codec_engine.decode_batch, blobs,
                        warmup=warmup, iters=iters)
        enc_img_per_s = b / (t_enc.median_us / 1e6)
        records.append(BenchRecord(
            label=f"batch_{b}",
            params={"batch": b, "height": size, "width": size,
                    "quality": QUALITY, "nbytes": nbytes},
            timings_us={"encode_pipelined": t_enc.to_json(),
                        "decode_pipelined": t_dec.to_json()},
            metrics={
                "enc_img_per_s": enc_img_per_s,
                "dec_img_per_s": b / (t_dec.median_us / 1e6),
                "enc_mb_per_s": b * mb / (t_enc.median_us / 1e6),
                "speedup_vs_reference": enc_img_per_s / ref_img_per_s,
            }))
    return records


def adversarial_blocks() -> list:
    """(dc_diff, ac) pairs exercising the symboliser's corner cases:
    max-magnitude amplitudes, all-zero blocks, and ZRL chains (shared
    by the ``--check-identical`` CI gate and the property tests)."""
    return [
        (np.array([0, 0, 0]), np.zeros((3, 63), np.int64)),
        (np.array([5]), np.eye(1, 63, 62, dtype=np.int64) * 32767),
        (np.array([-32767]), np.eye(1, 63, 40, dtype=np.int64) * -32767),
        (np.array([1]), np.eye(1, 63, 62, dtype=np.int64) * 3),
        (np.array([7]),
         np.tile([0] * 9 + [1], 7)[:63].reshape(1, 63).astype(np.int64)),
        (np.array([100]), np.full((1, 63), 255, np.int64)),
        (np.array([0]),
         np.concatenate([np.zeros(47, np.int64), [7],
                         np.zeros(15, np.int64)]).reshape(1, 63)),
    ]


def entropy_identity_violations(seed: int = 0, trials: int = 25) -> list:
    """Cases where the vectorized entropy path diverges from the scalar
    reference — the ``--check-identical`` CI gate (must return []).

    Checks, per case: symbol-stream equality, payload byte equality,
    and both decoders inverting the stream exactly, over ``trials``
    random batches (mixed density, full amplitude range) plus the
    :func:`adversarial_blocks`.
    """
    from repro.core.entropy import dense, huffman, rle
    rng = np.random.default_rng(seed)
    cases = []
    for t in range(trials):
        n = int(rng.integers(1, 24))
        ac = rng.integers(-32767, 32768, (n, 63))
        ac[rng.random((n, 63)) < rng.uniform(0.2, 0.995)] = 0
        dc = rng.integers(-32767, 32768, (n,))
        cases.append((f"random_{t}", dc, ac))
    cases += [(f"adversarial_{i}", dc, ac)
              for i, (dc, ac) in enumerate(adversarial_blocks())]

    bad = []
    for name, dc, ac in cases:
        vec = dense.dense_to_stream(dense.symbolize_dense(dc, ac))
        ref = rle.symbolize_reference(dc, ac)
        if not all(np.array_equal(a, b) for a, b in zip(vec, ref)):
            bad.append(f"{name}: symbol stream mismatch")
            continue
        if vectorized_encode_stream(dc, ac) != reference_encode_stream(
                dc, ac):
            bad.append(f"{name}: payload bytes mismatch")
            continue
        dc_f, ac_f = rle.symbol_frequencies(vec[0], vec[1])
        dc_t = huffman.build_table(dc_f)
        ac_t = huffman.build_table(ac_f)
        payload = rle.encode_payload(*vec, dc_t, ac_t)
        got = rle.decode_payload(payload, len(dc), dc_t, ac_t)
        want = rle.decode_payload_reference(payload, len(dc), dc_t, ac_t)
        if not (np.array_equal(got[0], want[0])
                and np.array_equal(got[1], want[1])):
            bad.append(f"{name}: decoder mismatch vs reference")
        elif not (np.array_equal(got[0], dc) and np.array_equal(got[1],
                                                                ac)):
            bad.append(f"{name}: decode does not invert encode")
    return bad


def packing_identity_violations(seed: int = 0, trials: int = 25) -> list:
    """Cases where a routed pack-bits backend diverges from the NumPy
    reference — the packing half of the ``--check-identical`` CI gate
    (must return []).

    Checks, per case, that the staged NumPy reference
    (:func:`repro.kernels.pack_bits.pack_bits_ref`) and the Pallas
    kernel (interpret mode off-TPU) both produce bytes identical to
    :func:`repro.core.entropy.bitio.pack_bits`, over ``trials`` random
    field streams (mixed widths 0..16, including zero-width amplitude
    slots) plus the codeword fields of the :func:`adversarial_blocks`;
    then that whole ``DCTZ`` streams framed through the routed Pallas
    packer are identical to the default path, under both embedded and
    shared table policies.
    """
    from repro.core import entropy
    from repro.core.entropy import bitio, huffman, rle
    from repro.kernels import pack_bits as pb
    rng = np.random.default_rng(seed)
    cases = []
    for t in range(trials):
        m = int(rng.integers(1, 600))
        widths = rng.integers(0, 17, m)
        # deliberately unmasked: only the low `widths` bits are payload,
        # and backends must agree on ignoring the stray high bits
        fields = rng.integers(0, 1 << 16, m)
        cases.append((f"random_{t}", fields, widths))
    for i, (dc, ac) in enumerate(adversarial_blocks()):
        syms = rle.symbolize_reference(dc, ac)
        dc_f, ac_f = rle.symbol_frequencies(syms[0], syms[1])
        fields, widths = rle.codeword_fields(
            *syms, huffman.build_table(dc_f), huffman.build_table(ac_f))
        cases.append((f"adversarial_{i}", fields, widths))

    bad = []
    for name, fields, widths in cases:
        want = bitio.pack_bits(fields, widths)
        if pb.pack_bits_ref(fields, widths) != want:
            bad.append(f"{name}: staged reference bytes mismatch")
            continue
        if pb.pack_bits(fields, widths, backend="pallas",
                        interpret=None) != want:
            bad.append(f"{name}: Pallas kernel bytes mismatch")

    # whole-stream check: the routed packer must frame identical DCTZ
    # containers under every table policy
    c = codec.compress(images.lena_like(32, 32), QUALITY)
    packer = functools.partial(pb.pack_bits, backend="pallas")
    for tables in ("auto", "embedded", "shared"):
        want = entropy.encode_qcoeffs(c.qcoeffs, QUALITY, "exact",
                                      (32, 32), tables=tables)
        got = entropy.encode_qcoeffs(c.qcoeffs, QUALITY, "exact",
                                     (32, 32), tables=tables,
                                     packer=packer)
        if got != want:
            bad.append(f"stream_{tables}: routed Pallas stream mismatch")
    return bad


def unpack_identity_violations(seed: int = 0, trials: int = 25) -> list:
    """Cases where a routed unpack-bits backend diverges from the scalar
    decode oracle — the decode half of the ``--check-identical`` CI
    gate (must return []).

    Checks, per case, that the staged NumPy reference
    (:func:`repro.kernels.unpack_bits.unpack_bits_ref`, at the default
    and at a boundary-straddling tile size) and the Pallas speculative
    kernel (interpret mode off-TPU) decode coefficients identical to
    :func:`repro.core.entropy.rle.decode_payload_reference`, over
    ``trials`` random batches plus the :func:`adversarial_blocks`; that
    truncated prefixes of those payloads are rejected with the same
    error type and message as the production LUT walk; and that whole
    ``DCTZ`` streams decoded through the routed unpacker match the
    default path under every table policy.
    """
    from repro.core import entropy
    from repro.core.entropy import bitio, huffman, rle
    from repro.kernels import unpack_bits as ub
    from repro.kernels.unpack_bits import ref as uref
    rng = np.random.default_rng(seed)
    cases = []
    for t in range(trials):
        n = int(rng.integers(1, 24))
        ac = rng.integers(-32767, 32768, (n, 63))
        ac[rng.random((n, 63)) < rng.uniform(0.2, 0.995)] = 0
        dc = rng.integers(-32767, 32768, (n,))
        cases.append((f"random_{t}", dc, ac))
    cases += [(f"adversarial_{i}", dc, ac)
              for i, (dc, ac) in enumerate(adversarial_blocks())]

    def outcome(fn, *args, **kw):
        try:
            dc_o, ac_o = fn(*args, **kw)
            return ("ok", dc_o.tobytes(), ac_o.tobytes())
        except (bitio.TruncatedStream, ValueError) as e:
            return (type(e).__name__, str(e))

    backends = [
        ("staged", lambda p, n, d, a: uref.unpack_bits_ref(p, n, d, a)),
        ("staged_tiled", lambda p, n, d, a: uref.unpack_bits_ref(
            p, n, d, a, tile_bits=64)),
        ("pallas", lambda p, n, d, a: ub.unpack_bits(
            p, n, d, a, backend="pallas", interpret=None)),
    ]
    bad = []
    for name, dc, ac in cases:
        syms = rle.symbolize_reference(dc, ac)
        dc_f, ac_f = rle.symbol_frequencies(syms[0], syms[1])
        dc_t = huffman.build_table(dc_f)
        ac_t = huffman.build_table(ac_f)
        payload = rle.encode_payload(*syms, dc_t, ac_t)
        want = outcome(rle.decode_payload_reference, payload, len(dc),
                       dc_t, ac_t)
        for bname, fn in backends:
            if outcome(fn, payload, len(dc), dc_t, ac_t) != want:
                bad.append(f"{name}: {bname} decode mismatch vs reference")
        # truncated prefixes must fail identically to the LUT walk
        # (the shipped backend): same error class, same bit offset
        for cut in (0, len(payload) // 2, len(payload) - 1):
            want = outcome(rle.decode_payload, payload[:cut], len(dc),
                           dc_t, ac_t)
            for bname, fn in backends:
                if outcome(fn, payload[:cut], len(dc), dc_t, ac_t) != want:
                    bad.append(f"{name}: {bname} truncation at byte "
                               f"{cut} not rejected identically")

    # whole-stream check: the routed unpacker must reproduce the
    # default decode of DCTZ containers under every table policy
    c = codec.compress(images.lena_like(32, 32), QUALITY)
    unpacker = functools.partial(ub.unpack_bits, backend="pallas")
    for tables in ("auto", "embedded", "shared"):
        stream = entropy.encode_qcoeffs(c.qcoeffs, QUALITY, "exact",
                                        (32, 32), tables=tables)
        want_z, want_hdr = entropy.decode_zigzag_host(stream)
        got_z, got_hdr = entropy.decode_zigzag_host(stream,
                                                    unpacker=unpacker)
        if not (np.array_equal(want_z, got_z) and want_hdr == got_hdr):
            bad.append(f"stream_{tables}: routed unpack stream mismatch")
    return bad


def symbolize_identity_violations(seed: int = 0, trials: int = 25) -> list:
    """Cases where a routed symbolize backend diverges from the scalar
    oracle — the symbolisation third of the ``--check-identical`` CI
    gate (must return []).

    Checks, per case, that the host symbolizer
    (:func:`repro.kernels.symbolize.ref.symbolize_ref`) and the Pallas
    kernel (interpret mode off-TPU) produce symbol streams element- and
    dtype-identical to
    :func:`repro.core.entropy.rle.symbolize_reference`, histograms
    bit-identical to :func:`repro.core.entropy.rle.symbol_frequencies`,
    and payload bytes identical to the scalar path, over ``trials``
    random batches plus the :func:`adversarial_blocks`; that levels too
    wide for a 15-bit amplitude are rejected with the oracle's exact
    :class:`repro.core.entropy.rle.RangeError` message on every
    backend; and that whole ``DCTZ`` streams framed through each routed
    symbolizer (v1 embedded-table and v2 shared/auto-negotiated framing
    alike) are byte-identical to the default path.
    """
    from repro.core import entropy
    from repro.core.entropy import dense, huffman, rle
    from repro.kernels import symbolize as sy
    from repro.kernels.symbolize import ref as sref
    rng = np.random.default_rng(seed)
    cases = []
    for t in range(trials):
        n = int(rng.integers(1, 24))
        ac = rng.integers(-32767, 32768, (n, 63))
        ac[rng.random((n, 63)) < rng.uniform(0.2, 0.995)] = 0
        dc = rng.integers(-32767, 32768, (n,))
        cases.append((f"random_{t}", dc, ac))
    cases += [(f"adversarial_{i}", dc, ac)
              for i, (dc, ac) in enumerate(adversarial_blocks())]

    backends = [
        ("staged", lambda d, a: sref.symbolize_ref(d, a)),
        ("pallas", lambda d, a: sy.symbolize(d, a, backend="pallas",
                                             interpret=None)),
    ]
    preps = [("numpy", dense.prepare),
             ("pallas", functools.partial(sy.prepare, backend="pallas"))]
    bad = []
    for name, dc, ac in cases:
        want = rle.symbolize_reference(dc, ac)
        for bname, fn in backends:
            got = fn(dc, ac)
            if not all(np.array_equal(a, b) and a.dtype == b.dtype
                       for a, b in zip(got, want)):
                bad.append(f"{name}: {bname} symbol stream mismatch")
        dc_f, ac_f = rle.symbol_frequencies(want[0], want[1])
        dc_t, ac_t = (huffman.build_table(dc_f), huffman.build_table(ac_f))
        want_payload = rle.encode_payload(*want, dc_t, ac_t)
        for bname, prepare in preps:
            prep = prepare(dc, ac)
            if not (np.array_equal(prep.dc_freq, dc_f)
                    and np.array_equal(prep.ac_freq, ac_f)):
                bad.append(f"{name}: {bname} histogram mismatch")
                continue
            if prep.payload(dc_t, ac_t) != want_payload:
                bad.append(f"{name}: {bname} payload bytes mismatch")

    # out-of-range levels must raise the oracle's exact RangeError on
    # every backend (the device guard routes them to the reference)
    def outcome(fn):
        try:
            fn()
            return None
        except rle.RangeError as e:
            return str(e)

    for rname, dc, ac in [
            ("dc_overflow", np.array([1 << 15]), np.zeros((1, 63))),
            ("ac_overflow", np.array([0]),
             np.eye(1, 63, 5, dtype=np.int64) * (1 << 15))]:
        want_err = outcome(lambda: rle.symbolize_reference(dc, ac))
        for bname, fn in backends:
            if outcome(lambda: fn(dc, ac)) != want_err:
                bad.append(f"{rname}: {bname} RangeError mismatch")
        for bname, prepare in preps:
            if outcome(lambda: prepare(dc, ac)) != want_err:
                bad.append(f"{rname}: {bname} prepared RangeError mismatch")

    # whole-stream check: each routed symbolizer must frame identical
    # DCTZ containers under every table policy (v1 embedded framing and
    # v2 shared/auto-negotiated framing, from the device histograms)
    c = codec.compress(images.lena_like(32, 32), QUALITY)
    for tables in ("auto", "embedded", "shared"):
        want_s = entropy.encode_qcoeffs(c.qcoeffs, QUALITY, "exact",
                                        (32, 32), tables=tables)
        for bname, prepare in preps:
            got_s = entropy.encode_qcoeffs(c.qcoeffs, QUALITY, "exact",
                                           (32, 32), tables=tables,
                                           symbolizer=prepare)
            if got_s != want_s:
                bad.append(f"stream_{tables}: routed {bname} "
                           f"symbolizer stream mismatch")
    return bad


ENTROPY_DECODE_GRID = {
    "smoke": {"sizes": [64, 128]},
    "paper": {"sizes": [128, 256]},
    "full": {"sizes": [256, 512]},
}


def entropy_decode_points(sizes, warmup: int, iters: int) -> list:
    """Measured records for the ``entropy_decode`` case.

    One record per image size, timing the same payload through every
    decode backend: the PR 3 scalar ``decode_payload_reference``, the
    PR 4 LUT walk (``decode_payload``), the staged speculative NumPy
    decode and the Pallas kernel in interpret mode (a correctness
    vehicle off-TPU, reported but not scored).  Two sizes per suite
    make the memory metrics comparable across payload lengths: the
    walk's decode tables (``walk_table_nbytes``) grow with every
    payload bit while the staged decoder's per-tile scratch
    (``staged_scratch_nbytes``) saturates at one tile + margin.

    Shared by the registry case and
    ``benchmarks/bench_entropy_throughput.py``.
    """
    from repro.core.entropy import rle
    from repro.kernels import unpack_bits as ub
    from repro.kernels.unpack_bits import ref as uref

    records = []
    for size in sizes:
        (z, dc_diff, ac, payload, (dc_t, ac_t),
         n_blocks) = _entropy_stage_inputs(size)
        nbits = len(payload) * 8
        t_ref = measure(rle.decode_payload_reference, payload, n_blocks,
                        dc_t, ac_t, warmup=min(warmup, 1),
                        iters=max(iters // 2, 2))
        t_walk = measure(rle.decode_payload, payload, n_blocks, dc_t,
                         ac_t, warmup=warmup, iters=iters)
        t_staged = measure(uref.unpack_bits_ref, payload, n_blocks, dc_t,
                           ac_t, warmup=warmup, iters=iters)
        t_pallas = measure(
            lambda: ub.unpack_bits(payload, n_blocks, dc_t, ac_t,
                                   backend="pallas", interpret=None),
            warmup=min(warmup, 1), iters=max(iters // 2, 2))
        records.append(BenchRecord(
            label=f"entropy_decode_{size}",
            params={"height": size, "width": size, "image": "lena",
                    "quality": QUALITY, "n_blocks": n_blocks,
                    "payload_nbits": nbits},
            timings_us={"dec_reference": t_ref.to_json(),
                        "dec_lut_walk": t_walk.to_json(),
                        "dec_staged": t_staged.to_json(),
                        "dec_pallas_interpret": t_pallas.to_json()},
            metrics={
                "staged_speedup_vs_reference":
                    t_ref.median_us / t_staged.median_us,
                "staged_speedup_vs_walk":
                    t_walk.median_us / t_staged.median_us,
                "dec_mb_per_s": (size * size / 1e6)
                    / (t_staged.median_us / 1e6),
                "walk_table_nbytes": rle.walk_table_nbytes(nbits),
                "staged_scratch_nbytes": uref.scratch_nbytes(nbits),
                "scratch_vs_walk":
                    uref.scratch_nbytes(nbits)
                    / rle.walk_table_nbytes(nbits),
            }))
    return records


@benchmark("entropy_decode", suites=("smoke", "paper", "full"),
           description="staged speculative decode vs scalar reference + "
                       "bounded decoder scratch vs per-bit LUT walk")
def entropy_decode(ctx: RunContext) -> list:
    """Decode-side counterpart of ``entropy_throughput``: the staged
    speculative decoder vs the scalar reference and the LUT walk on one
    payload per size, plus the decoder-memory metrics the unpack_bits
    design bounds (per-tile scratch, not per-payload-bit tables)."""
    grid = ENTROPY_DECODE_GRID.get(ctx.suite, ENTROPY_DECODE_GRID["paper"])
    timer = ctx.timer.scaled(warmup=max(ctx.timer.warmup, 1))
    return entropy_decode_points(grid["sizes"], warmup=timer.warmup,
                                 iters=timer.iters)


@benchmark("entropy_throughput", suites=("smoke", "paper", "full"),
           description="vectorized vs reference entropy coding MB/s + "
                       "overlapped encode_batch/decode_batch scaling")
def entropy_throughput(ctx: RunContext) -> list:
    """Host entropy stage in isolation (vectorized vs the PR 3 scalar
    reference) plus the engine's overlapped byte path across batch
    sizes; ``speedup_vs_reference`` scores the whole pipeline against
    the single-image reference encode rate."""
    grid = ENTROPY_GRID.get(ctx.suite, ENTROPY_GRID["paper"])
    timer = ctx.timer.scaled(warmup=max(ctx.timer.warmup, 1))
    return entropy_throughput_points(grid["size"], grid["batches"],
                                     warmup=timer.warmup,
                                     iters=timer.iters)


# ---------------------------------------------------------------------------
# Serving-layer coverage
# ---------------------------------------------------------------------------

def batch_throughput_grid(transforms, size: int, batches, iters: int) -> dict:
    """Best-of-N images/sec per (transform, batch) via the serving engine.

    The N timing rounds are *interleaved* across batch sizes so machine-
    load drift (shared CI runners) biases every batch size equally
    instead of whichever one it happened to land on.

    Args:
        transforms: iterable of codec transforms ("exact", "cordic", ...).
        size: square image side per batch element.
        batches: increasing batch sizes to sweep.
        iters: timing rounds per (transform, batch) point.

    Returns:
        transform -> {batch: img_per_s} with the best round kept.
    """
    from repro.serve import codec_engine
    batches = list(batches)
    base = np.stack([images.lena_like(size, size, seed=i)
                     for i in range(max(batches))])
    out = {}
    for transform in transforms:
        def run(x, transform=transform):
            rec, _ = codec_engine.roundtrip_batch(x, QUALITY, transform,
                                                  with_psnr=False)
            return rec

        best = {b: float("inf") for b in batches}
        for b in batches:                       # compile + warm every shape
            for _ in range(2):
                jax.block_until_ready(run(base[:b]))
        for _ in range(iters):
            for b in batches:
                t0 = time.perf_counter()
                jax.block_until_ready(run(base[:b]))
                best[b] = min(best[b], time.perf_counter() - t0)
        out[transform] = {b: b / best[b] for b in batches}
    return out


def check_monotone(per_batch: dict, up_to: int = 64) -> list:
    """Violations of strictly-increasing throughput for batches <= up_to.

    Args:
        per_batch: {batch: img_per_s} as one value of
            :func:`batch_throughput_grid`'s result.
        up_to: largest batch size the monotonicity claim covers (beyond
            it the backend may saturate).

    Returns:
        (smaller_batch, larger_batch) pairs where throughput did not grow.
    """
    checked = sorted(b for b in per_batch if b <= up_to)
    return [(a, b) for a, b in zip(checked, checked[1:])
            if per_batch[b] <= per_batch[a]]


@benchmark("serve_batch_throughput", suites=("smoke", "paper", "full"),
           description="images/sec vs batch size through codec_engine")
def serve_batch_throughput(ctx: RunContext) -> list:
    batches = batch_sizes(BATCH_GRID.get(ctx.suite, BATCH_GRID["paper"]))
    iters = {"smoke": 3, "paper": 8}.get(ctx.suite, 15)
    size = 8    # the paper's atomic block: dispatch overhead dominates,
    #             which is exactly what batching amortises
    grid = batch_throughput_grid(("exact", "cordic"), size, batches, iters)
    return [BenchRecord(
        label=f"batch_{b}",
        params={"batch": b, "size": size, "quality": QUALITY},
        metrics={f"img_per_s_{t}": grid[t][b] for t in grid})
        for b in batches]


RAGGED_SHAPES = {
    "smoke": [(200, 200), (96, 80), (200, 200)],
    "paper": [(200, 200), (320, 288), (512, 480), (96, 80), (64, 48),
              (200, 200), (1024, 814)],
}
RAGGED_SHAPES["full"] = RAGGED_SHAPES["paper"]


@benchmark("serve_ragged", suites=("smoke", "paper", "full"),
           description="ragged mixed-size batch through codec_engine "
                       "bucketing")
def serve_ragged(ctx: RunContext) -> list:
    """Mixed-size list in one call: bucketed shapes, grouped compilation."""
    from repro.serve import codec_engine
    shapes = RAGGED_SHAPES.get(ctx.suite, RAGGED_SHAPES["paper"])
    imgs = [images.lena_like(h, w, seed=i)
            for i, (h, w) in enumerate(shapes)]
    cb = codec_engine.compress_batch(imgs, QUALITY, "exact")
    n_buckets = len(cb.groups)

    def run():
        rec, _ = codec_engine.roundtrip_batch(imgs, QUALITY, "exact",
                                              with_psnr=False)
        return rec

    t = measure(run, warmup=max(ctx.timer.warmup, 1), iters=ctx.timer.iters)
    return [BenchRecord(
        label=f"ragged_{len(imgs)}imgs",
        params={"n_images": len(imgs), "quality": QUALITY,
                "shapes": [list(s) for s in shapes],
                "bucket": codec_engine.SHAPE_BUCKET},
        timings_us={"roundtrip": t.to_json()},
        metrics={"n_buckets": n_buckets,
                 "img_per_s": len(imgs) / (t.median_us / 1e6)})]


SERVICE_TRAFFIC_GRID = {
    "smoke": {"size": 48, "n_requests": 60, "loads": (0.5, 1.0, 2.0)},
    "paper": {"size": 64, "n_requests": 150, "loads": (0.5, 1.0, 2.0)},
    "full": {"size": 64, "n_requests": 300,
             "loads": (0.25, 0.5, 1.0, 2.0, 4.0)},
}

TRAFFIC_QUALITIES = (30, 75)


def _traffic_pool(size: int, variants: int = 6) -> list:
    """Mixed-size image pool; reuse across requests exercises the cache."""
    pool = []
    for i in range(variants):
        gen = images.lena_like if i % 2 == 0 else images.cablecar_like
        h = size - 8 * (i % 2)
        w = size - 6 * (i % 3)
        pool.append(np.asarray(gen(h, w, seed=i)))
    return pool


def calibrate_service_step(pool, max_batch: int) -> float:
    """Measured seconds for one full engine batch (per-level capacity).

    Warms every (shape bucket, quality) combination the traffic will
    hit (compile time must not pollute latency percentiles), then times
    a full ``max_batch`` encode — the model step the offered-load
    multiples are expressed against.
    """
    from repro.serve import codec_engine
    # adaptive batching can dispatch ANY batch size 1..max_batch, and
    # first calls at a new size still compile (beyond the engine's pow2
    # batch padding, the entropy edge specialises further) — a cold
    # compile landing in the bucket EWMA would poison admission for the
    # whole run, so warm every (size, quality) combination
    for b in range(1, max_batch + 1):
        batch = [pool[i % len(pool)] for i in range(b)]
        for q in TRAFFIC_QUALITIES:
            codec_engine.encode_batch(batch, q)
    batch = [pool[i % len(pool)] for i in range(max_batch)]
    t0 = time.perf_counter()
    codec_engine.encode_batch(batch, TRAFFIC_QUALITIES[0])
    return time.perf_counter() - t0


def service_traffic_points(size: int, n_requests: int, loads,
                           max_batch: int = 8, seed: int = 0) -> list:
    """Open-loop Poisson traffic through :class:`CodecService`.

    Arrivals are scheduled at precomputed absolute times, independent
    of completions — the standard open-loop methodology for offered
    load/goodput curves, where clients must keep offering load even
    when the service falls behind (a closed-loop client would slow
    down with the server and never drive it past saturation).

    For each offered-load level (a multiple of the measured engine
    capacity ``max_batch / step_s``), a fresh service is driven with
    ``n_requests`` Poisson arrivals of mixed sizes and qualities under
    a deadline of ``8 x step_s``, and the record reports the SLO view:
    p50/p99 client latency, goodput (served within deadline per
    second), reject rate by admission reason, cache hit rate, and the
    batch-occupancy histogram (how full dispatched engine batches ran).

    Shared by the ``service_traffic`` registry case and
    ``benchmarks/bench_service_traffic.py`` (whose ``--check`` gates
    outcome conservation in CI).
    """
    import asyncio

    from repro.serve.admission import RejectedError
    from repro.serve.service import (CodecService, EngineFailure,
                                     ServiceConfig)

    pool = _traffic_pool(size)
    step_s = calibrate_service_step(pool, max_batch)
    capacity_rps = max_batch / step_s
    deadline_s = 8 * step_s
    cfg_kw = dict(max_batch=max_batch,
                  max_wait_s=min(max(step_s / 2, 0.001), 0.05),
                  max_queue_depth=4 * max_batch,
                  initial_step_s=step_s,
                  default_deadline_s=deadline_s)

    async def run_level(offered_rps: float, rng) -> tuple:
        arrivals = np.cumsum(rng.exponential(1.0 / offered_rps,
                                             n_requests))
        outcomes: list = []

        async def one(at: float, img, quality: int):
            await asyncio.sleep(at)
            t0 = time.perf_counter()
            try:
                resp = await svc.submit(img, quality=quality)
                outcomes.append(("served", time.perf_counter() - t0,
                                 resp.deadline_missed, resp.cache_hit))
            except RejectedError as exc:
                outcomes.append((f"rejected:{exc.reason}",
                                 time.perf_counter() - t0, False, False))
            except EngineFailure:
                outcomes.append(("failed", time.perf_counter() - t0,
                                 False, False))

        async with CodecService(ServiceConfig(**cfg_kw)) as svc:
            t_start = time.perf_counter()
            await asyncio.gather(*[
                one(float(arrivals[i]),
                    pool[int(rng.integers(len(pool)))],
                    TRAFFIC_QUALITIES[int(rng.integers(
                        len(TRAFFIC_QUALITIES)))])
                for i in range(n_requests)])
            makespan = time.perf_counter() - t_start
        return outcomes, makespan, svc.stats

    records = []
    for load in loads:
        rng = np.random.default_rng(seed)
        offered = load * capacity_rps
        outcomes, makespan, stats = asyncio.run(run_level(offered, rng))
        served = [o for o in outcomes if o[0] == "served"]
        lat_ms = sorted(o[1] * 1e3 for o in served)
        in_deadline = sum(1 for o in served if not o[2])
        rejects = [o for o in outcomes if o[0].startswith("rejected:")]

        def pct(p):
            if not lat_ms:
                return float("nan")
            return lat_ms[min(len(lat_ms) - 1,
                              round(p / 100 * (len(lat_ms) - 1)))]

        records.append(BenchRecord(
            label=f"load_{load:g}x",
            params={"offered_load": load, "offered_rps": offered,
                    "capacity_rps": capacity_rps,
                    "step_ms": step_s * 1e3,
                    "deadline_ms": deadline_s * 1e3,
                    "n_requests": n_requests, "size": size,
                    "max_batch": max_batch,
                    "qualities": list(TRAFFIC_QUALITIES),
                    "occupancy": {str(k): v for k, v in
                                  sorted(stats.occupancy.items())},
                    "rejected_by_reason": dict(stats.rejected)},
            metrics={
                "p50_ms": pct(50),
                "p99_ms": pct(99),
                "goodput_rps": in_deadline / makespan,
                "reject_rate": len(rejects) / n_requests,
                "served": float(len(served)),
                "deadline_missed": float(stats.deadline_missed),
                "failed": float(stats.failed),
                "cache_hit_rate": (sum(1 for o in served if o[3])
                                   / max(len(served), 1)),
                "mean_batch_occupancy": (
                    sum(k * v for k, v in stats.occupancy.items())
                    / max(sum(stats.occupancy.values()), 1)),
            }))
    return records


def traffic_conservation_violations(records) -> list:
    """CI-gate checks for ``service_traffic`` records.

    Every offered request must reach exactly one terminal outcome
    (served + rejected + failed == n_requests — the bench completing at
    all already rules out a dispatch deadlock), and the occupancy
    histogram must account for every non-cache-hit served request.

    Returns:
        Human-readable violation strings (empty == gate passes).
    """
    out = []
    for rec in records:
        n = rec.params["n_requests"]
        served = rec.metrics["served"]
        rejected = rec.metrics["reject_rate"] * n
        failed = rec.metrics["failed"]
        total = served + rejected + failed
        if abs(total - n) > 1e-6:
            out.append(f"{rec.label}: {total:g} outcomes for {n} "
                       f"requests (served {served:g} + rejected "
                       f"{rejected:g} + failed {failed:g})")
        occ = sum(int(k) * v for k, v in
                  rec.params["occupancy"].items())
        hits = round(rec.metrics["cache_hit_rate"] * max(served, 1))
        if occ + hits + failed < served:
            out.append(f"{rec.label}: occupancy accounts for {occ} "
                       f"requests + {hits} cache hits < {served:g} "
                       f"served")
    return out


@benchmark("service_traffic", suites=("smoke", "paper", "full"),
           description="open-loop Poisson traffic through the async "
                       "service: p50/p99 latency, goodput, reject rate")
def service_traffic(ctx: RunContext) -> list:
    """The serving SLO view the straight-line benches cannot give:
    latency percentiles, goodput and shed load at offered loads below,
    at, and above the engine's measured capacity, through the
    deadline-aware batching service (docs/serving.md)."""
    grid = SERVICE_TRAFFIC_GRID.get(ctx.suite,
                                    SERVICE_TRAFFIC_GRID["paper"])
    return service_traffic_points(grid["size"], grid["n_requests"],
                                  grid["loads"])


SERVICE_CHAOS_GRID = {
    "smoke": {"size": 48, "n_requests": 80, "load": 1.0},
    "paper": {"size": 64, "n_requests": 160, "load": 1.0},
    "full": {"size": 64, "n_requests": 240, "load": 1.5},
}

#: Fault-kind coverage the chaos gate requires (every kind must fire).
CHAOS_FAULT_KINDS = ("fail", "latency", "corrupt", "kill")


def chaos_fault_plan(step_s: float, timeout_s: float, seed: int = 0):
    """The seeded fault storm the chaos bench replays, by call index.

    Phases are indexed by **engine-call number** (not wall time), so the
    same (plan, seed) injects the same faults regardless of scheduler
    jitter: a clean warm-up, an exception storm long enough to trip the
    breaker *and* feed its half-open probes (probes consume call
    indices, so the closed→open→half-open→closed cycle completes
    deterministically in call space), a clean recovery window, latency
    spikes past the attempt timeout, one worker death, a corruption
    burst (every payload byte-flipped — the CRC validator must catch
    all of them), then a clean tail that drains the retry backlog.
    """
    from repro.serve.chaos import FaultPhase, FaultPlan
    return FaultPlan(phases=(
        # exception storm: trips the breaker by call 3 (min_calls=4,
        # threshold 0.5); the first half-open probe lands on call 4
        # (fails, re-opens), later probes land in the clean window
        # [5, 8) and close the breaker — cycle provable in call space
        FaultPhase(start=2, stop=5, fail_rate=1.0),
        FaultPhase(start=8, stop=9, latency_rate=1.0,
                   latency_s=2.0 * timeout_s),
        FaultPhase(start=9, stop=10, kill_rate=1.0),
        FaultPhase(start=10, stop=12, corrupt_rate=1.0),
    ), seed=seed)


def service_chaos_points(size: int, n_requests: int, load: float,
                         max_batch: int = 4, seed: int = 0) -> list:
    """Open-loop Poisson traffic through a *resilient* service under a
    scripted fault storm (engine exceptions, latency spikes past the
    attempt timeout, worker death, payload byte flips).

    Same methodology as :func:`service_traffic_points` — arrivals at
    precomputed absolute times against the calibrated engine capacity —
    but the engine is wrapped in the deterministic
    :class:`repro.serve.chaos.ChaosEngine` and the service runs with
    the full resilience envelope: bounded retries, per-attempt
    timeouts, a circuit breaker, CRC payload validation
    (:func:`repro.serve.chaos.dctz_crc_ok`) and graceful degradation.

    The record carries everything :func:`chaos_violations` CI-gates:
    outcome conservation, the breaker's transition log, injected-fault
    coverage, the unhandled-exception guard counter, and byte identity
    of every served payload against serial ``encode_batch``.

    Shared by the ``service_chaos`` registry case and
    ``benchmarks/bench_service_chaos.py --check``.
    """
    import asyncio

    from repro.serve import codec_engine
    from repro.serve.admission import RejectedError
    from repro.serve.chaos import ChaosEngine, dctz_crc_ok
    from repro.serve.resilience import (BreakerConfig, DegradeConfig,
                                        ResilienceConfig, RetryPolicy)
    from repro.serve.service import (CodecService, EngineFailure,
                                     ServiceConfig)

    pool = _traffic_pool(size)
    step_s = calibrate_service_step(pool, max_batch)
    capacity_rps = max_batch / step_s
    offered_rps = load * capacity_rps
    timeout_s = max(6 * step_s, 0.05)
    deadline_s = max(24 * step_s, 5 * timeout_s)
    plan = chaos_fault_plan(step_s, timeout_s, seed=seed)

    def inner(imgs, quality):
        return codec_engine.encode_batch(list(imgs), quality)

    eng = ChaosEngine(inner, plan)
    cfg = ServiceConfig(
        max_batch=max_batch,
        max_wait_s=min(max(step_s / 2, 0.001), 0.05),
        max_queue_depth=4 * max_batch,
        initial_step_s=step_s,
        default_deadline_s=deadline_s,
        # the traffic reuses ~a dozen (image, quality) pairs — a warm
        # cache would absorb nearly every request and starve the fault
        # phases of engine calls, so the chaos run disables it
        cache_entries=0,
        # a timed-out attempt abandons its worker thread until the
        # engine returns; a second worker keeps the service moving
        # through the latency-spike phase
        engine_concurrency=2,
        resilience=ResilienceConfig(
            timeout_s=timeout_s,
            retry=RetryPolicy(max_attempts=3,
                              backoff_base_s=step_s / 4,
                              backoff_cap_s=2 * step_s,
                              budget_rate=2 * offered_rps,
                              budget_burst=2 * max_batch * 4),
            breaker=BreakerConfig(window=8, min_calls=4,
                                  failure_threshold=0.5,
                                  reset_timeout_s=2 * step_s,
                                  half_open_max_calls=1,
                                  half_open_successes=2),
            # level-1 cap = 30, already in TRAFFIC_QUALITIES: degraded
            # encodes hit warm compilations only
            degrade=DegradeConfig(quality_caps=(100, 30),
                                  urgent_batch_caps=(None, 2),
                                  enter_pressure=0.85,
                                  exit_pressure=0.3,
                                  sustain_s=step_s,
                                  cool_s=4 * step_s),
            validate_payload=dctz_crc_ok,
            seed=seed))

    async def run_storm(rng) -> tuple:
        arrivals = np.cumsum(rng.exponential(1.0 / offered_rps,
                                             n_requests))
        outcomes: list = []
        served_payloads: list = []      # (pool_idx, quality, payload)

        async def one(at: float, pool_idx: int, quality: int):
            await asyncio.sleep(at)
            t0 = time.perf_counter()
            try:
                resp = await svc.submit(pool[pool_idx], quality=quality)
                outcomes.append(("served", time.perf_counter() - t0,
                                 resp.deadline_missed))
                served_payloads.append((pool_idx, resp.quality,
                                        resp.payload))
            except RejectedError as exc:
                outcomes.append((f"rejected:{exc.reason}",
                                 time.perf_counter() - t0, False))
            except EngineFailure:
                outcomes.append(("failed", time.perf_counter() - t0,
                                 False))

        async with CodecService(cfg, engine=eng) as svc:
            t_start = time.perf_counter()
            await asyncio.gather(*[
                one(float(arrivals[i]),
                    int(rng.integers(len(pool))),
                    TRAFFIC_QUALITIES[int(rng.integers(
                        len(TRAFFIC_QUALITIES)))])
                for i in range(n_requests)])
            makespan = time.perf_counter() - t_start
        return outcomes, served_payloads, makespan, svc

    rng = np.random.default_rng(seed)
    outcomes, served_payloads, makespan, svc = asyncio.run(
        run_storm(rng))
    stats = svc.stats

    # byte identity: every successfully served payload must match the
    # serial single-image encode exactly — resilience may delay or shed
    # work, never alter it
    byte_mismatches = 0
    reference: dict = {}
    for pool_idx, quality, payload in served_payloads:
        k = (pool_idx, quality)
        if k not in reference:
            reference[k] = inner([pool[pool_idx]], quality)[0]
        if payload != reference[k]:
            byte_mismatches += 1

    served = [o for o in outcomes if o[0] == "served"]
    lat_ms = sorted(o[1] * 1e3 for o in served)
    in_deadline = sum(1 for o in served if not o[2])
    rejects = [o for o in outcomes if o[0].startswith("rejected:")]

    def pct(p):
        if not lat_ms:
            return float("nan")
        return lat_ms[min(len(lat_ms) - 1,
                          round(p / 100 * (len(lat_ms) - 1)))]

    transitions = [[t, frm, to] for t, frm, to in
                   svc.breaker.transitions]
    return [BenchRecord(
        label=f"storm_{load:g}x",
        params={"offered_load": load, "offered_rps": offered_rps,
                "capacity_rps": capacity_rps,
                "step_ms": step_s * 1e3,
                "timeout_ms": timeout_s * 1e3,
                "deadline_ms": deadline_s * 1e3,
                "n_requests": n_requests, "size": size,
                "max_batch": max_batch, "seed": seed,
                "qualities": list(TRAFFIC_QUALITIES),
                "engine_calls": eng.calls,
                "fault_events": eng.event_counts(),
                "breaker_transitions": transitions,
                "rejected_by_reason": dict(stats.rejected),
                "dispatcher_ok": svc.dispatcher_error is None},
        metrics={
            "p50_ms": pct(50),
            "p99_ms": pct(99),
            "goodput_rps": in_deadline / makespan,
            "served": float(len(served)),
            "reject_rate": len(rejects) / n_requests,
            "failed": float(stats.failed),
            "retries": float(stats.retries),
            "retry_rate": stats.retries / n_requests,
            "timeouts": float(stats.timeouts),
            "corrupt_caught": float(stats.corrupt_payloads),
            "degraded_served": float(stats.degraded_served),
            "closed_unserved": float(stats.closed_unserved),
            "unhandled": float(stats.unhandled),
            "byte_mismatches": float(byte_mismatches),
        })]


def chaos_violations(records) -> list:
    """CI-gate checks for ``service_chaos`` records.

    The resilience acceptance criteria, checked per record: outcome
    conservation (served + rejected + failed == n_requests, degraded ⊆
    served), zero byte mismatches against serial encode, zero unhandled
    exceptions escaping the dispatch loop, a live dispatcher at close,
    a provable closed→open→half-open→closed breaker cycle, and every
    scripted fault kind having actually fired.

    Returns:
        Human-readable violation strings (empty == gate passes).
    """
    out = []
    for rec in records:
        n = rec.params["n_requests"]
        served = rec.metrics["served"]
        rejected = rec.metrics["reject_rate"] * n
        failed = rec.metrics["failed"]
        total = served + rejected + failed
        if abs(total - n) > 1e-6:
            out.append(f"{rec.label}: {total:g} outcomes for {n} "
                       f"requests (served {served:g} + rejected "
                       f"{rejected:g} + failed {failed:g})")
        if rec.metrics["degraded_served"] > served:
            out.append(f"{rec.label}: degraded_served "
                       f"{rec.metrics['degraded_served']:g} exceeds "
                       f"served {served:g}")
        if rec.metrics["byte_mismatches"]:
            out.append(f"{rec.label}: "
                       f"{rec.metrics['byte_mismatches']:g} served "
                       f"payloads differ from serial encode_batch")
        if rec.metrics["unhandled"]:
            out.append(f"{rec.label}: {rec.metrics['unhandled']:g} "
                       f"unhandled exceptions escaped batch handling")
        if not rec.params["dispatcher_ok"]:
            out.append(f"{rec.label}: dispatcher crashed during the run")
        if rec.metrics["closed_unserved"]:
            out.append(f"{rec.label}: "
                       f"{rec.metrics['closed_unserved']:g} futures "
                       f"dangling at close")
        cycle = ["closed", "open", "half_open", "closed"]
        trans = rec.params["breaker_transitions"]
        # the visited-state sequence: every from-state plus the final
        # to-state; the required cycle must appear as a subsequence
        states = [frm for _, frm, _ in trans]
        if trans:
            states.append(trans[-1][2])
        i = 0
        for s in states:
            if i < len(cycle) and s == cycle[i]:
                i += 1
        if i < len(cycle):
            out.append(f"{rec.label}: breaker never completed the "
                       f"closed→open→half-open→closed cycle "
                       f"(transitions: "
                       f"{rec.params['breaker_transitions']})")
        fired = rec.params["fault_events"]
        for kind in CHAOS_FAULT_KINDS:
            if not fired.get(kind):
                out.append(f"{rec.label}: scripted fault kind "
                           f"{kind!r} never fired "
                           f"({rec.params['engine_calls']} engine "
                           f"calls)")
    return out


@benchmark("service_chaos", suites=("smoke", "paper", "full"),
           description="seeded fault storm through the resilient "
                       "service: goodput, retry rate, breaker cycle, "
                       "byte-identical payloads")
def service_chaos(ctx: RunContext) -> list:
    """The failure-mode view the clean traffic bench cannot give: how
    goodput, latency and shed load behave through an engine exception
    storm, timeout-tripping latency spikes, a worker death and a
    payload-corruption burst — with retries, circuit breaking, CRC
    validation and graceful degradation turned on (docs/serving.md)."""
    grid = SERVICE_CHAOS_GRID.get(ctx.suite, SERVICE_CHAOS_GRID["paper"])
    return service_chaos_points(grid["size"], grid["n_requests"],
                                grid["load"])


# ---------------------------------------------------------------------------
# Framework micro-benches (suite "micro"; also in --full runs)
# ---------------------------------------------------------------------------

@benchmark("framework_micro", suites=("micro", "full"),
           description="fusion win, grad/KV DCT compression, decode step")
def framework_micro(ctx: RunContext) -> list:
    """Micro-benches of the framework pieces built around the codec."""
    import functools

    from repro.kernels import grad_dct

    records = []

    # --- fusion: unfused 3-pass (paper's kernel structure) vs fused 1-pass
    img = jnp.asarray(images.lena_like(1024, 1024), jnp.float32)
    q = quant.qtable(QUALITY)

    @jax.jit
    def unfused(img):
        x = img - 128.0
        coef = dct.blockwise_dct2d_kron(x)          # pass 1 (DCT kernel)
        qc = jnp.round(coef / q) * q                # pass 2 (quantiser)
        return dct.blockwise_idct2d_kron(qc) + 128  # pass 3 (IDCT kernel)

    @jax.jit
    def fused(img):
        x = img - 128.0
        t = dct.kron_dct_matrix(8)
        blocks = dct.to_blocks(x).reshape(-1, 64)
        coef = blocks @ t.T
        qv = q.reshape(64)
        qc = jnp.round(coef / qv) * qv
        rec = (qc @ t).reshape(128, 128, 8, 8)
        return dct.from_blocks(rec) + 128.0

    t_u = measure(unfused, img, warmup=1, iters=5)
    t_f = measure(fused, img, warmup=1, iters=5)
    records.append(BenchRecord(
        label="fused_codec_1024",
        params={"height": 1024, "width": 1024, "quality": QUALITY},
        timings_us={"fused": t_f.to_json(), "unfused": t_u.to_json()},
        metrics={"fusion_speedup": t_u.median_us / t_f.median_us}))

    # --- gradient DCT compression roundtrip
    g = jax.random.normal(jax.random.key(0), (4 * 1024 * 1024,))
    fn = jax.jit(functools.partial(grad_dct.roundtrip, keep=16,
                                   interpret=None))
    t_g = measure(fn, g, warmup=1, iters=3)
    cg = grad_dct.encode(g, keep=16)
    mb = g.size * 4 / 1e6
    records.append(BenchRecord(
        label="grad_dct_roundtrip_16MB",
        params={"elements": g.size, "keep": 16},
        timings_us={"roundtrip": t_g.to_json()},
        metrics={"mb_per_s": mb / (t_g.median_us / 1e6),
                 "wire_ratio": g.size * 4 / cg.wire_bytes()}))

    # --- KV-cache DCT compression roundtrip
    from repro.serve import kv_compress
    cache = {"k": jax.random.normal(jax.random.key(1),
                                    (4, 2, 512, 4, 32), jnp.bfloat16),
             "v": jax.random.normal(jax.random.key(2),
                                    (4, 2, 512, 4, 32), jnp.bfloat16)}
    raw = sum(v.size * v.dtype.itemsize for v in cache.values())

    def kv_roundtrip(c):
        ckv, tails = kv_compress.compress_cache(c, keep=16, prefix_len=512)
        return kv_compress.reconstruct_cache(ckv, tails)

    t_kv = measure(kv_roundtrip, cache, warmup=1, iters=3)
    ckv, tails = kv_compress.compress_cache(cache, keep=16, prefix_len=512)
    comp = kv_compress.wire_bytes(ckv, tails)
    records.append(BenchRecord(
        label="kv_dct_roundtrip",
        params={"keep": 16, "prefix_len": 512},
        timings_us={"roundtrip": t_kv.to_json()},
        metrics={"hbm_ratio": raw / comp}))

    # --- LM decode-step throughput (reduced config)
    from repro.configs import registry as R
    from repro.models import registry as M
    from repro.serve import engine
    cfg = R.reduced("smollm-360m", n_layers=4, d_model=128, vocab_size=1024)
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, batch=8, max_len=256)
    step = engine.make_decode_step(cfg)
    tok = jnp.zeros((8, 1), jnp.int32)
    key = jax.random.key(0)
    fn = lambda: step(params, tok, cache, jnp.asarray(128, jnp.int32), key)
    t_d = measure(fn, warmup=2, iters=5)
    records.append(BenchRecord(
        label="decode_step_b8_reduced",
        params={"batch": 8, "n_layers": 4, "d_model": 128},
        timings_us={"step": t_d.to_json()},
        metrics={"tok_per_s": 8 / (t_d.median_us / 1e6)}))
    return records


# ---------------------------------------------------------------------------
# Codec-kernel roofline: achieved FLOP/s and bytes/s vs documented peaks
# ---------------------------------------------------------------------------

ROOFLINE_GRID = {
    "smoke": {"size": 64, "entropy_size": 48},
    "paper": {"size": 256, "entropy_size": 128},
    "full": {"size": 512, "entropy_size": 256},
}


def kernel_cost_terms(fn, *args) -> tuple:
    """(flops, bytes_accessed) from XLA's lowered cost analysis of ``fn``.

    ``cost_analysis()`` returns a dict on newer jax and a one-element
    list of dicts on 0.4.x CPU; both forms are handled.  Missing terms
    count as zero (interpret-mode Pallas bodies, for instance, report
    nothing — that is why the roofline lowers the *jnp reference*
    implementations, which XLA can fully analyse).
    """
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    cost = cost or {}
    return (float(cost.get("flops", 0.0) or 0.0),
            float(cost.get("bytes accessed", 0.0) or 0.0))


def roofline_points(size: int, entropy_size: int, warmup: int,
                    iters: int) -> list:
    """Measured records for the ``roofline`` case.

    One record per routed kernel: wall time of the *routed* call (tile
    knobs at ``None``, so the tuned-tile artifact applies when valid),
    FLOP and byte counts from XLA cost analysis of the kernel's jnp
    reference at the same shape (analytic byte counts for the two
    bit-stream kernels, whose FLOP content is ~0), and achieved
    GFLOP/s / GB/s against the documented peaks of the chip the run is
    on (:func:`repro.launch.mesh.chip_peaks`, keyed by device kind; a
    TPU without an entry raises).  Off the TPU there is no peak to
    divide by, so the peak fractions and the bound are left out.

    Shared by the registry case and ``benchmarks/roofline.py``.
    """
    from repro.core.entropy import dense
    from repro.kernels import pack_bits as pb
    from repro.kernels import unpack_bits as ub
    from repro.kernels.cordic_loeffler import ops as cl_ops
    from repro.kernels.cordic_loeffler import ref as cl_ref
    from repro.kernels.dct8x8 import ops as d_ops
    from repro.kernels.dct8x8 import ref as d_ref
    from repro.kernels.fused_codec import ops as f_ops
    from repro.kernels.fused_codec import ref as f_ref
    from repro.launch import mesh

    peaks = (mesh.chip_peaks() if jax.devices()[0].platform == "tpu"
             else None)
    img = jnp.asarray(images.lena_like(size, size), jnp.float32)
    f32 = img.size * 4

    points = []

    def add(kernel, run, flops, nbytes, params):
        t = measure(run, warmup=warmup, iters=iters)
        sec = t.median_us / 1e6
        achieved_flops = flops / sec
        achieved_bw = nbytes / sec
        intensity = flops / nbytes if nbytes else float("inf")
        metrics = {
            "flops": flops,
            "bytes_accessed": nbytes,
            "achieved_gflop_s": achieved_flops / 1e9,
            "achieved_gb_s": achieved_bw / 1e9,
            "intensity_flop_per_byte": intensity,
        }
        if peaks is not None:
            # Ridge point: intensity above flops_peak/bw_peak is
            # compute-bound.
            ridge = peaks["peak_flops_bf16"] / peaks["hbm_bw"]
            metrics.update(
                frac_peak_flops=achieved_flops / peaks["peak_flops_bf16"],
                frac_peak_bw=achieved_bw / peaks["hbm_bw"],
                compute_bound=float(intensity > ridge))
        points.append(BenchRecord(
            label=kernel,
            params={"kernel": kernel, **params},
            timings_us={"routed": t.to_json()},
            metrics=metrics))

    fl, by = kernel_cost_terms(d_ref.dct8x8_ref, img)
    add("dct8x8", lambda: d_ops.dct8x8(img), fl, by,
        {"height": size, "width": size})

    fl, by = kernel_cost_terms(cl_ref.cordic_loeffler_ref, img)
    add("cordic_loeffler", lambda: cl_ops.cordic_loeffler_dct(img), fl, by,
        {"height": size, "width": size})

    fl, by = kernel_cost_terms(f_ref.fused_codec_ref, img)
    add("fused_codec", lambda: f_ops.fused_codec(img), fl, by,
        {"height": size, "width": size, "quality": QUALITY})

    (_, dc_diff, ac, payload, (dc_t, ac_t),
     n_blocks) = _entropy_stage_inputs(entropy_size)
    codes, lengths = dense.encode_fields_dense(
        dense.symbolize_dense(dc_diff, ac), dc_t, ac_t)
    nbits = len(payload) * 8

    # The bit kernels are pure data movement: FLOP content ~0, byte
    # traffic is analytic — three int32 field columns in, payload out
    # (pack); bit windows in, three per-offset word planes out (unpack).
    pack_bytes = 3 * codes.size * 4 + len(payload)
    add("pack_bits",
        lambda: pb.pack_bits(codes, lengths, backend="pallas"),
        0.0, float(pack_bytes),
        {"entropy_size": entropy_size, "fields": int(codes.size),
         "payload_bits": nbits})

    unpack_bytes = (nbits + 1) * 4 + 3 * (nbits + 1) * 4
    add("unpack_bits",
        lambda: ub.unpack_bits(payload, n_blocks, dc_t, ac_t,
                               backend="pallas"),
        0.0, float(unpack_bytes),
        {"entropy_size": entropy_size, "payload_bits": nbits,
         "n_blocks": n_blocks})
    return points


@benchmark("roofline", suites=("smoke", "paper", "full"),
           description="per-kernel achieved FLOP/s and bytes/s from XLA "
                       "cost analysis vs documented per-chip peaks")
def roofline(ctx: RunContext) -> list:
    """Achieved-vs-peak view of every routed codec kernel: the paper's
    computational-efficiency claim expressed as roofline coordinates
    instead of speedup-vs-reference."""
    grid = ROOFLINE_GRID.get(ctx.suite, ROOFLINE_GRID["paper"])
    timer = ctx.timer.scaled(warmup=max(ctx.timer.warmup, 1))
    return roofline_points(grid["size"], grid["entropy_size"],
                           warmup=timer.warmup, iters=timer.iters)
