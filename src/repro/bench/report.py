"""Render ``RESULTS.md`` — the paper's Tables 1-4 and the serving
curves — from benchmark JSON artifacts alone.

The renderer is a pure function of the artifacts: no benchmark re-runs,
no imports of jax.  ``render(results)`` returns the markdown;
:func:`write_results` places it at ``RESULTS.md``.  Section <-> artifact
mapping (see docs/benchmarks.md):

========================  =========================================
artifact (case name)      RESULTS.md section
========================  =========================================
table1_lena               Table 1 — codec time vs Lena size
table2_cablecar           Table 2 — codec time vs Cable-car size
table3_psnr_lena          Table 3 — PSNR exact vs Cordic (Lena)
table4_psnr_cablecar      Table 4 — PSNR exact vs Cordic (Cable-car)
rate_distortion           Rate–distortion (measured bytes)
entropy_throughput        Entropy throughput (vectorized host coding)
entropy_decode            Entropy decode (speculative unpack backends)
serve_batch_throughput    Batch throughput curve (serving engine)
serve_ragged              Ragged mixed-size batches (serving engine)
service_traffic           Open-loop service traffic (async service)
service_chaos             Fault-storm traffic (resilient service)
autotune                  Kernel tile autotuning (sweep winners)
roofline                  Kernel roofline (achieved vs peak)
framework_micro           Framework micro-benches
========================  =========================================
"""

from __future__ import annotations

import pathlib


def _ms(timing: dict) -> str:
    return f"{timing['median_us'] / 1e3:.3f}"


def _size(rec) -> str:
    return f"{rec.params.get('height', '?')}x{rec.params.get('width', '?')}"


def _timing_table(result, title: str, blurb: str) -> str:
    lines = [f"## {title}", "", blurb, "",
             "| image | size | serial (ms) | parallel (ms) | speedup "
             "| MPix/s |",
             "|---|---|---|---|---|---|"]
    for r in result.records:
        lines.append(
            f"| {r.params.get('image', result.name)} | {_size(r)} "
            f"| {_ms(r.timings_us['serial'])} "
            f"| {_ms(r.timings_us['parallel'])} "
            f"| {r.metrics['speedup']:.1f}x "
            f"| {r.metrics['mpix_per_s']:.1f} |")
    return "\n".join(lines)


def _psnr_table(result, title: str, blurb: str) -> str:
    lines = [f"## {title}", "", blurb, "",
             "| image | size | exact DCT (dB) | Cordic-Loeffler (dB) "
             "| gap (dB) |",
             "|---|---|---|---|---|"]
    for r in result.records:
        lines.append(
            f"| {r.params.get('image', result.name)} | {_size(r)} "
            f"| {r.metrics['psnr_db_exact']:.3f} "
            f"| {r.metrics['psnr_db_cordic']:.3f} "
            f"| {r.metrics['gap_db']:.3f} |")
    return "\n".join(lines)


def _rd_table(result) -> str:
    lines = ["## Rate–distortion (measured bytes)", "",
             "Quality sweep through the complete codec — DCT, quantise, "
             "zig-zag, run-length, canonical Huffman, `DCTZ` container "
             "(`repro.core.entropy`).  Bits-per-pixel are *measured* "
             "from the entropy-coded stream, never an estimator; "
             "encode is image→bytes, decode is "
             "bytes→image.", "",
             "| image | size | quality | bits/px | ratio | PSNR (dB) "
             "| encode (ms) | decode (ms) |",
             "|---|---|---|---|---|---|---|---|"]
    for r in result.records:
        lines.append(
            f"| {r.params.get('image', result.name)} | {_size(r)} "
            f"| {r.params['quality']} "
            f"| {r.metrics['bpp']:.3f} "
            f"| {r.metrics['compression_ratio']:.1f}x "
            f"| {r.metrics['psnr_db']:.2f} "
            f"| {_ms(r.timings_us['encode'])} "
            f"| {_ms(r.timings_us['decode'])} |")
    return "\n".join(lines)


def _entropy_table(result) -> str:
    stage = [r for r in result.records if r.label.startswith("entropy_")]
    stages = [r for r in result.records
              if r.label.startswith("encode_stages_")]
    batches = [r for r in result.records if r.label.startswith("batch_")]
    lines = ["## Entropy throughput (vectorized host coding)", "",
             "The host entropy stage (`repro.core.entropy.dense` to "
             "encode, the `rle` LUT walk to decode) measured against "
             "the scalar per-block reference it replaced, plus the "
             "serving engine's overlapped byte path "
             "(`encode_batch`/`decode_batch`: device DCT/quant for "
             "bucket *k+1* in flight while a thread pool entropy-codes "
             "bucket *k*).  `speedup vs ref` scores the engine's encode "
             "against the single-image reference end-to-end encode "
             "rate — growth with batch size is the overlap win.", ""]
    for r in stage:
        lines += [
            f"Single image {_size(r)} (quality {r.params['quality']}, "
            f"{r.params['n_blocks']} blocks, "
            f"{r.params['payload_nbytes']} payload bytes):", "",
            "| direction | vectorized (ms) | reference (ms) | speedup "
            "| MB/s |",
            "|---|---|---|---|---|",
            f"| encode | {_ms(r.timings_us['enc_vectorized'])} "
            f"| {_ms(r.timings_us['enc_reference'])} "
            f"| {r.metrics['enc_speedup']:.1f}x "
            f"| {r.metrics['enc_mb_per_s']:.1f} |",
            f"| decode | {_ms(r.timings_us['dec_vectorized'])} "
            f"| {_ms(r.timings_us['dec_reference'])} "
            f"| {r.metrics['dec_speedup']:.1f}x "
            f"| {r.metrics['dec_mb_per_s']:.1f} |", ""]
    for r in stages:
        lines += [
            f"Per-stage encode breakdown {_size(r)} (staged pipeline; "
            f"`symbolize` is the host symbolizer's fused pass; "
            f"transfer compares the coefficient bytes the host path "
            f"pulls per image against the histograms+payload the "
            f"device-resident TPU chain ships):", "",
            "| stage | median (ms) |",
            "|---|---|",
            f"| symbolize (fused) | "
            f"{_ms(r.timings_us['stage_symbolize'])} |",
            f"| table choice | {_ms(r.timings_us['stage_table_choice'])} |",
            f"| codeword lookup | {_ms(r.timings_us['stage_codeword'])} |",
            f"| bit packing | {_ms(r.timings_us['stage_pack'])} |", "",
            f"Transfer per image: "
            f"{r.metrics['host_transfer_bytes_per_image']:.0f} B host "
            f"coefficients vs "
            f"{r.metrics['device_transfer_bytes_per_image']:.0f} B "
            f"device (histograms + payload) — "
            f"{r.metrics['transfer_reduction']:.1f}x less traffic.", ""]
    if batches:
        lines += [
            "| batch | enc img/s | dec img/s | enc MB/s "
            "| speedup vs ref |",
            "|---|---|---|---|---|"]
        for r in batches:
            lines.append(
                f"| {r.params['batch']} "
                f"| {r.metrics['enc_img_per_s']:.1f} "
                f"| {r.metrics['dec_img_per_s']:.1f} "
                f"| {r.metrics['enc_mb_per_s']:.1f} "
                f"| {r.metrics['speedup_vs_reference']:.2f}x |")
    return "\n".join(lines).rstrip()


def _entropy_decode_table(result) -> str:
    lines = ["## Entropy decode (speculative unpack backends)", "",
             "Payload-bits → coefficients through the routed unpack "
             "backends (`repro.kernels.unpack_bits`): the staged NumPy "
             "speculative decode (decode from every bit offset, pointer "
             "doubling, per-tile emission) and the Pallas kernel in "
             "interpret mode, against the scalar `decode_payload_"
             "reference` oracle and the vectorized LUT walk "
             "(`rle.decode_payload`).  Interpret-mode Pallas timings are "
             "a correctness vehicle off-TPU, reported but not scored.  "
             "`scratch` is the staged decoder's per-tile working set — "
             "bounded by the tile size — vs the LUT walk's tables, which "
             "grow with payload bits.", "",
             "| size | payload (bits) | reference (ms) | LUT walk (ms) "
             "| staged (ms) | staged vs ref | staged vs walk "
             "| scratch / walk tables |",
             "|---|---|---|---|---|---|---|---|"]
    for r in result.records:
        lines.append(
            f"| {_size(r)} | {r.params['payload_nbits']} "
            f"| {_ms(r.timings_us['dec_reference'])} "
            f"| {_ms(r.timings_us['dec_lut_walk'])} "
            f"| {_ms(r.timings_us['dec_staged'])} "
            f"| {r.metrics['staged_speedup_vs_reference']:.1f}x "
            f"| {r.metrics['staged_speedup_vs_walk']:.2f}x "
            f"| {r.metrics['staged_scratch_nbytes'] / 1024:.0f} KiB / "
            f"{r.metrics['walk_table_nbytes'] / 1024:.0f} KiB |")
    return "\n".join(lines)


def _throughput_table(result) -> str:
    transforms = sorted({k[len("img_per_s_"):]
                         for r in result.records for k in r.metrics
                         if k.startswith("img_per_s_")})
    head = " | ".join(f"{t} (img/s)" for t in transforms)
    lines = ["## Batch throughput (serving engine)", "",
             "Images/sec vs batch size through "
             "`codec_engine.roundtrip_batch` — the paper's GPU-saturation "
             "win, realised here as dispatch-overhead amortisation; one "
             f"image is {result.records[0].params.get('size', 8)}px square.",
             "",
             f"| batch | {head} |",
             "|---|" + "---|" * len(transforms)]
    for r in result.records:
        cells = " | ".join(f"{r.metrics[f'img_per_s_{t}']:.1f}"
                           for t in transforms)
        lines.append(f"| {r.params['batch']} | {cells} |")
    return "\n".join(lines)


def _ragged_table(result) -> str:
    lines = ["## Ragged mixed-size batches (serving engine)", "",
             "A list of mixed-size images in one `roundtrip_batch` call: "
             "shapes bucket up to multiples of "
             f"{result.records[0].params.get('bucket', 64)}px, equal "
             "buckets compile once and run together.", "",
             "| images | distinct buckets | roundtrip (ms) | img/s |",
             "|---|---|---|---|"]
    for r in result.records:
        lines.append(
            f"| {r.params['n_images']} | {r.metrics['n_buckets']:.0f} "
            f"| {_ms(r.timings_us['roundtrip'])} "
            f"| {r.metrics['img_per_s']:.1f} |")
    return "\n".join(lines)


def _service_traffic_table(result) -> str:
    p0 = result.records[0].params
    lines = ["## Open-loop service traffic (async batching service)", "",
             "Open-loop Poisson arrivals through the deadline-aware "
             f"batching service ({p0['n_requests']} requests per level, "
             f"{p0['size']}px image pool, per-request deadline "
             f"{p0['deadline_ms']:.0f} ms, max_batch {p0['max_batch']}). "
             "Offered load is a multiple of the engine's calibrated "
             f"capacity ({p0['capacity_rps']:.0f} req/s); below capacity "
             "the service batches for latency, above it the admission "
             "bound and deadline sweep shed load instead of queueing "
             "without bound (docs/serving.md).", "",
             "| offered load | p50 (ms) | p99 (ms) | goodput (req/s) "
             "| rejected | late | cache hits | mean batch |",
             "|---|---|---|---|---|---|---|---|"]
    for r in result.records:
        m = r.metrics
        lines.append(
            f"| {r.params['offered_load']:g}x "
            f"| {m['p50_ms']:.1f} | {m['p99_ms']:.1f} "
            f"| {m['goodput_rps']:.0f} "
            f"| {m['reject_rate'] * 100:.0f}% "
            f"| {m['deadline_missed']:.0f} "
            f"| {m['cache_hit_rate'] * 100:.0f}% "
            f"| {m['mean_batch_occupancy']:.1f} |")
    return "\n".join(lines)


def _service_chaos_table(result) -> str:
    lines = ["## Fault-storm traffic (resilient service)", ""]
    for r in result.records:
        p, m = r.params, r.metrics
        faults = ", ".join(f"{k} x{v}" for k, v in
                           sorted(p["fault_events"].items()))
        cycle = " → ".join([p["breaker_transitions"][0][1]] +
                           [t[2] for t in p["breaker_transitions"]]) \
            if p["breaker_transitions"] else "none"
        lines += [
            "Open-loop Poisson traffic at "
            f"{p['offered_load']:g}x calibrated capacity "
            f"({p['n_requests']} requests, {p['size']}px pool, "
            f"deadline {p['deadline_ms']:.0f} ms, attempt timeout "
            f"{p['timeout_ms']:.0f} ms) while a seeded call-indexed "
            f"fault plan injects {faults} across {p['engine_calls']} "
            "engine calls.  The resilience envelope (bounded retries, "
            "circuit breaker, CRC payload validation, graceful "
            "degradation) keeps every outcome conserved and every "
            "served payload byte-identical to serial encode "
            "(docs/serving.md); the chaos gate in CI enforces it.", "",
            "| offered load | p50 (ms) | p99 (ms) | goodput (req/s) "
            "| served | rejected | failed | retries | timeouts "
            "| corrupt caught | byte mismatches |",
            "|---|---|---|---|---|---|---|---|---|---|---|",
            f"| {p['offered_load']:g}x | {m['p50_ms']:.1f} "
            f"| {m['p99_ms']:.1f} | {m['goodput_rps']:.0f} "
            f"| {m['served']:.0f} | {m['reject_rate'] * 100:.0f}% "
            f"| {m['failed']:.0f} | {m['retries']:.0f} "
            f"| {m['timeouts']:.0f} | {m['corrupt_caught']:.0f} "
            f"| {m['byte_mismatches']:.0f} |", "",
            f"Breaker cycle: {cycle}.",
        ]
    return "\n".join(lines)


def _tuning_table(result) -> str:
    lines = ["## Kernel tile autotuning", "",
             "Pow2 tile sweep per (kernel, shape bucket) on backend "
             f"`{result.environment.get('backend', '?')}` "
             "(`python -m repro.bench autotune`).  Winners persist to "
             "`results/tuning.json`; each kernel's `ops.py` router loads "
             "them when its tile knob is left at `None` — on a different "
             "backend the artifact is rejected and built-in defaults "
             "apply.  Identity across every candidate is pinned by the "
             "tile-invariance property tests, so tuning can only change "
             "speed, never bits.", "",
             "| kernel | bucket | winner | best (ms) | vs default "
             "| candidates swept |",
             "|---|---|---|---|---|---|"]
    from repro.kernels import tuning
    for r in result.records:
        kernel = r.params["kernel"]
        param = tuning.PARAM_OF.get(kernel, "tile")
        vs = r.metrics.get("speedup_vs_default")
        lines.append(
            f"| {kernel} | {r.params['bucket']} "
            f"| {param}={r.params[param]} "
            f"| {r.metrics['best_us'] / 1e3:.3f} "
            f"| {f'{vs:.2f}x' if vs is not None else '—'} "
            f"| {len(r.timings_us)} |")
    return "\n".join(lines)


def _roofline_table(result) -> str:
    lines = ["## Kernel roofline (achieved vs peak)", "",
             "Achieved FLOP/s and bytes/s of every routed codec kernel: "
             "wall time of the routed call (tuned tiles when a valid "
             "artifact applies) against FLOP/byte counts from XLA's "
             "lowered cost analysis of the jnp reference at the same "
             "shape (analytic byte counts for the two bit-stream "
             "kernels).  Peaks are the documented per-chip terms of "
             "the device the run was on (`repro.launch.mesh.PEAKS`); "
             "off the TPU the peak columns read \"not measured\".", "",
             "| kernel | shape | time (ms) | GFLOP/s | GB/s "
             "| % peak FLOPs | % peak BW | FLOP/byte | bound |",
             "|---|---|---|---|---|---|---|---|---|"]
    for r in result.records:
        m = r.metrics
        if "height" in r.params:
            shape = f"{r.params['height']}x{r.params['width']}"
        else:
            shape = f"{r.params['payload_bits']} bits"
        if "compute_bound" in m:
            bound = "compute" if m["compute_bound"] else "memory"
            fracs = (f"{m['frac_peak_flops'] * 100:.4f}% "
                     f"| {m['frac_peak_bw'] * 100:.4f}%")
        else:
            bound = "not measured"
            fracs = "not measured | not measured"
        lines.append(
            f"| {r.params['kernel']} | {shape} "
            f"| {_ms(r.timings_us['routed'])} "
            f"| {m['achieved_gflop_s']:.2f} "
            f"| {m['achieved_gb_s']:.2f} "
            f"| {fracs} "
            f"| {m['intensity_flop_per_byte']:.2f} "
            f"| {bound} |")
    return "\n".join(lines)


def _micro_table(result) -> str:
    lines = ["## Framework micro-benches", "",
             "| bench | time (ms) | derived |",
             "|---|---|---|"]
    for r in result.records:
        leg, timing = next(iter(r.timings_us.items()))
        derived = "; ".join(f"{k}={v:.2f}" for k, v in r.metrics.items())
        lines.append(f"| {r.label} ({leg}) | {_ms(timing)} | {derived} |")
    return "\n".join(lines)


_TIMING_BLURBS = {
    "table1_lena": ("Paper Table 1 (Lena): per-block sequential codec (the "
                    "paper's CPU code shape) vs the batched serving path "
                    "(fused kernel on TPU, staged batch path elsewhere)."),
    "table2_cablecar": ("Paper Table 2 (Cable-car): same legs as Table 1 on "
                        "the paper's Cable-car sizes."),
}
_PSNR_BLURBS = {
    "table3_psnr_lena": ("Paper Table 3 (Lena): reconstruction quality of "
                         "the exact DCT vs the Cordic-based Loeffler DCT at "
                         "quality 50; the ~2 dB ordering and the size trend "
                         "are the reproduction targets."),
    "table4_psnr_cablecar": ("Paper Table 4 (Cable-car): as Table 3 on the "
                             "edge-rich Cable-car image (lower PSNR at equal "
                             "quality, matching the paper's ordering)."),
}

_SECTIONS = (
    ("table1_lena", "Table 1 — DCT codec time vs Lena image size"),
    ("table2_cablecar", "Table 2 — DCT codec time vs Cable-car image size"),
    ("table3_psnr_lena", "Table 3 — PSNR, exact DCT vs Cordic-Loeffler "
                         "(Lena)"),
    ("table4_psnr_cablecar", "Table 4 — PSNR, exact DCT vs Cordic-Loeffler "
                             "(Cable-car)"),
    ("rate_distortion", None),
    ("entropy_throughput", None),
    ("entropy_decode", None),
    ("serve_batch_throughput", None),
    ("serve_ragged", None),
    ("service_traffic", None),
    ("service_chaos", None),
    ("autotune", None),
    ("roofline", None),
    ("framework_micro", None),
)


def render(results) -> str:
    """Markdown report from loaded artifacts.

    Args:
        results: iterable of :class:`repro.bench.schema.BenchResult`
            (any subset; sections render only for present artifacts,
            always in paper-table order).

    Returns:
        The full RESULTS.md text, environment header included.
    """
    by_name = {r.name: r for r in results}
    if not by_name:
        raise ValueError("no artifacts to render; run "
                         "`python -m repro.bench run --suite paper` first")
    env = next(iter(by_name.values())).environment
    suites = sorted({r.suite for r in by_name.values() if r.suite})
    parts = [
        "# RESULTS",
        "Regenerated from benchmark JSON artifacts by "
        "`python -m repro.bench report` — do not edit by hand; see "
        "docs/benchmarks.md for the artifact schema and the "
        "section-to-artifact mapping.",
        f"*Environment:* backend=`{env.get('backend', '?')}` "
        f"devices={env.get('device_count', '?')} "
        f"jax={env.get('jax_version', '?')} "
        f"git=`{env.get('git_sha', '?')}` "
        f"at {env.get('timestamp_utc', '?')} "
        f"(suite{'s' if len(suites) != 1 else ''}: "
        f"{', '.join(suites) or '?'})",
        "Absolute times are whatever this backend delivers (the paper "
        "measured a Core i7 vs a GTX 480); the reproduction targets are "
        "the *trends* — time growth with image size, serial/parallel "
        "ratio, PSNR ordering and the exact-vs-Cordic gap.",
    ]
    for name, title in _SECTIONS:
        if name not in by_name:
            continue
        result = by_name[name]
        if name in _TIMING_BLURBS:
            parts.append(_timing_table(result, title, _TIMING_BLURBS[name]))
        elif name in _PSNR_BLURBS:
            parts.append(_psnr_table(result, title, _PSNR_BLURBS[name]))
        elif name == "rate_distortion":
            parts.append(_rd_table(result))
        elif name == "entropy_throughput":
            parts.append(_entropy_table(result))
        elif name == "entropy_decode":
            parts.append(_entropy_decode_table(result))
        elif name == "serve_batch_throughput":
            parts.append(_throughput_table(result))
        elif name == "serve_ragged":
            parts.append(_ragged_table(result))
        elif name == "service_traffic":
            parts.append(_service_traffic_table(result))
        elif name == "service_chaos":
            parts.append(_service_chaos_table(result))
        elif name == "autotune":
            parts.append(_tuning_table(result))
        elif name == "roofline":
            parts.append(_roofline_table(result))
        elif name == "framework_micro":
            parts.append(_micro_table(result))
    extra = sorted(set(by_name) - {n for n, _ in _SECTIONS})
    if extra:
        parts.append("## Other artifacts\n\n" + "\n".join(
            f"- `{n}`: {len(by_name[n].records)} records "
            f"(no renderer section)" for n in extra))
    return "\n\n".join(parts) + "\n"


def write_results(results, out_path: str = "RESULTS.md") -> pathlib.Path:
    """Render and write the report; returns the written path."""
    path = pathlib.Path(out_path)
    path.write_text(render(results))
    return path
