"""Kernel tile autotuner: sweep pow2 candidates, persist the winners.

``python -m repro.bench autotune`` times every pow2 tile candidate per
(kernel, backend, shape bucket) through the standard warmup/steady-state
timer (:func:`repro.bench.timer.measure`) and writes two artifacts:

* ``results/tuning.json`` — the versioned, git-sha-stamped winners
  document (:mod:`repro.kernels.tuning` schema) that each kernel's
  ``ops.py`` router loads when its tile knob is left at ``None``;
* ``results/autotune.json`` — a standard :class:`~repro.bench.schema.
  BenchResult` carrying the full candidate-vs-time grid, so the
  RESULTS.md renderer can show *why* each winner won.

The swept knobs are exactly the ones the routers expose: ``tile`` (the
``common.pick_tile`` target) for ``dct8x8`` / ``cordic_loeffler`` /
``fused_codec``, ``tile_bits`` (window follows as
``tile_bits + margin``) for ``pack_bits`` / ``unpack_bits``,
``block_rows`` for ``grad_dct``, and ``tile_blocks`` for
``symbolize``.  Off-TPU
the Pallas legs run in interpret mode — the sweep then measures the
interpreter, which is still a full pipeline proof (CI runs it with
``--smoke``); winners are only *routed* on the backend they were swept
on (:func:`repro.kernels.tuning.lookup` rejects backend mismatches).

Correctness never depends on the sweep: the tile-invariance property
tests (``tests/test_tile_invariance.py``) pin byte/coefficient identity
across every candidate listed here, so the autotuner can only change
speed, not bits.
"""

from __future__ import annotations

import pathlib

import numpy as np

from repro.bench import schema
from repro.bench.schema import BenchRecord
from repro.bench.timer import TimerConfig, measure
from repro.kernels import tuning

# Every tile candidate the autotuner may select, per kernel.  The
# tile-invariance tests import this dict: adding a candidate here
# automatically widens the identity gate.
CANDIDATES = {
    "dct8x8": (8, 16, 32, 64, 128, 256),
    "cordic_loeffler": (8, 16, 32, 64, 128, 256),
    "fused_codec": (8, 16, 32, 64, 128, 256),
    "pack_bits": (256, 512, 1024, 2048, 4096),
    "unpack_bits": (512, 1024, 2048, 4096, 8192),
    "grad_dct": (64, 128, 256, 512, 1024),
    "symbolize": (8, 16, 32, 64, 128),
}

# Suite -> sweep grid.  ``image_buckets`` are square image sizes (the
# pow2 shape buckets tuned entries are keyed by); ``entropy_size`` is
# the image size whose real entropy payload drives the bit-kernel
# sweeps; ``max_candidates`` trims each candidate list from the top
# (smoke keeps the sweep tiny for CI).
SUITE_GRIDS = {
    "smoke": {"image_buckets": (64,), "entropy_size": 48,
              "grad_rows": 256, "max_candidates": 2},
    "paper": {"image_buckets": (256,), "entropy_size": 128,
              "grad_rows": 4096, "max_candidates": None},
    "full": {"image_buckets": (256, 512), "entropy_size": 256,
             "grad_rows": 16384, "max_candidates": None},
}

SUITE_TIMERS = {
    "smoke": TimerConfig(warmup=1, iters=2),
    "paper": TimerConfig(warmup=1, iters=3),
    "full": TimerConfig(warmup=1, iters=3),
}

IMAGE_KERNELS = ("dct8x8", "cordic_loeffler", "fused_codec")


def _image_candidates(kernel: str, bucket: int, cap: int | None) -> list:
    cands = [c for c in CANDIDATES[kernel] if c <= bucket]
    return cands[-cap:] if cap else cands


def _bit_candidates(kernel: str, cap: int | None) -> list:
    cands = list(CANDIDATES[kernel])
    return cands[:cap] if cap else cands


def _image_fn(kernel: str):
    if kernel == "dct8x8":
        from repro.kernels.dct8x8 import ops
        return lambda img, t: ops.dct8x8(img, tile=t)
    if kernel == "cordic_loeffler":
        from repro.kernels.cordic_loeffler import ops
        return lambda img, t: ops.cordic_loeffler_dct(img, tile=t)
    from repro.kernels.fused_codec import ops
    return lambda img, t: ops.fused_codec(img, tile=t)


def _entropy_workload(size: int):
    """One real image's entropy stage: (codes, lengths, payload, tables,
    n_blocks, dc_diff, ac).  The pack sweep times the captured codeword
    fields; the unpack sweep times the payload they packed into; the
    symbolize sweep re-symbolises the raw block arrays."""
    from repro.bench import cases
    from repro.core.entropy import dense
    (_, dc_diff, ac, payload, (dc_t, ac_t),
     n_blocks) = cases._entropy_stage_inputs(size)
    codes, lengths = dense.encode_fields_dense(
        dense.symbolize_dense(dc_diff, ac), dc_t, ac_t)
    return codes, lengths, payload, (dc_t, ac_t), n_blocks, dc_diff, ac


def sweep(suite: str = "paper", timer: TimerConfig | None = None,
          log=print) -> list:
    """Time every candidate; one :class:`BenchRecord` per (kernel, bucket).

    Record layout: ``params`` carries kernel/bucket/winner, ``timings_us``
    one leg per candidate (``tile_<n>``), ``metrics`` the winning median
    and its speedup over the built-in default tile.
    """
    from repro.core import images

    grid = SUITE_GRIDS.get(suite, SUITE_GRIDS["paper"])
    timer = timer or SUITE_TIMERS.get(suite, TimerConfig(warmup=1, iters=3))
    cap = grid["max_candidates"]
    records = []

    for kernel in IMAGE_KERNELS:
        fn = _image_fn(kernel)
        for bucket in grid["image_buckets"]:
            img = np.asarray(images.lena_like(bucket, bucket),
                             dtype=np.float32)
            records.append(_sweep_one(
                kernel, tuning.bucket_of(bucket),
                _image_candidates(kernel, bucket, cap),
                lambda t, f=fn, x=img: f(x, t), timer, log,
                extra_params={"image_hw": bucket}))

    size = grid["entropy_size"]
    codes, lengths, payload, (dc_t, ac_t), n_blocks, dc_diff, ac = (
        _entropy_workload(size))
    nbits = len(payload) * 8

    from repro.kernels import pack_bits as pb
    from repro.kernels import unpack_bits as ub
    total_bits = int(np.sum(lengths))
    records.append(_sweep_one(
        "pack_bits", tuning.bucket_of(total_bits),
        _bit_candidates("pack_bits", cap),
        lambda t: pb.pack_bits(codes, lengths, backend="pallas",
                               tile_bits=t),
        timer, log, extra_params={"entropy_size": size,
                                  "payload_bits": total_bits}))
    records.append(_sweep_one(
        "unpack_bits", tuning.bucket_of(nbits),
        _bit_candidates("unpack_bits", cap),
        lambda t: ub.unpack_bits(payload, n_blocks, dc_t, ac_t,
                                 backend="pallas", tile_bits=t),
        timer, log, extra_params={"entropy_size": size,
                                  "payload_bits": nbits,
                                  "n_blocks": n_blocks}))

    # symbolize: same image's zig-zag blocks through the Pallas kernel
    # (interpret mode off-TPU), keyed by block count like the routers
    from repro.kernels import symbolize as sy
    records.append(_sweep_one(
        "symbolize", tuning.bucket_of(n_blocks),
        [c for c in _bit_candidates("symbolize", cap) if c <= n_blocks]
        or [CANDIDATES["symbolize"][0]],
        lambda t: sy.symbolize_dense(dc_diff, ac, backend="pallas",
                                     tile_blocks=t),
        timer, log, extra_params={"entropy_size": size,
                                  "n_blocks": n_blocks}))

    # grad_dct: a flat gradient vector (the distributed-training
    # compressor), keyed by 64-sample row count
    from repro.kernels import grad_dct as gd
    rows = grid["grad_rows"]
    g = np.asarray(np.random.default_rng(0).standard_normal(
        rows * gd.BLOCK + 7), dtype=np.float32)
    # measure() blocks on the returned pytree; CompressedGrad is a plain
    # dataclass, so hand its arrays back as a tuple
    records.append(_sweep_one(
        "grad_dct", tuning.bucket_of(rows),
        [c for c in _bit_candidates("grad_dct", cap) if c <= rows]
        or [CANDIDATES["grad_dct"][0]],
        lambda t: (lambda cg: (cg.q, cg.scale, cg.tail))(
            gd.encode(g, block_rows=t)),
        timer, log, extra_params={"grad_rows": rows}))
    return records


def _sweep_one(kernel: str, bucket: int, candidates, run_candidate,
               timer: TimerConfig, log, extra_params: dict) -> BenchRecord:
    param = tuning.PARAM_OF[kernel]
    default = tuning.DEFAULTS[kernel][param]
    timings = {}
    for cand in candidates:
        t = measure(run_candidate, cand,
                    warmup=timer.warmup, iters=timer.iters)
        timings[f"tile_{cand}"] = t.to_json()
    best = min(timings, key=lambda k: timings[k]["median_us"])
    winner = int(best.split("_", 1)[1])
    best_us = timings[best]["median_us"]
    default_key = f"tile_{default}"
    metrics = {"best_us": best_us}
    if default_key in timings:
        metrics["speedup_vs_default"] = (
            timings[default_key]["median_us"] / best_us)
    log(f"autotune {kernel} bucket={bucket}: {param}={winner} "
        f"({best_us:.0f} us over {len(timings)} candidates)")
    return BenchRecord(
        label=f"{kernel}_b{bucket}",
        params={"kernel": kernel, "bucket": bucket, param: winner,
                "candidates": list(candidates), **extra_params},
        timings_us=timings,
        metrics=metrics)


def tuning_entries(records) -> list:
    """Winner entries (the :mod:`repro.kernels.tuning` schema) from
    sweep records."""
    entries = []
    for r in records:
        kernel = r.params["kernel"]
        param = tuning.PARAM_OF[kernel]
        entries.append({
            "kernel": kernel,
            "bucket": int(r.params["bucket"]),
            "params": {param: int(r.params[param])},
            "best_us": r.metrics["best_us"],
        })
    return entries


def run_autotune(suite: str = "paper", out_dir: str = "results",
                 timer: TimerConfig | None = None, log=print) -> dict:
    """Full autotune run: sweep, write both artifacts, reload the cache.

    Returns ``{"tuning_path": ..., "bench_path": ..., "records": ...}``.
    """
    env = schema.capture_environment()
    log(f"# autotune suite={suite} backend={env['backend']} "
        f"git={env['git_sha']}")
    records = sweep(suite, timer=timer, log=log)

    doc = tuning.make_doc(tuning_entries(records), backend=env["backend"],
                          environment=env)
    tuning_path = tuning.save(doc, pathlib.Path(out_dir) / "tuning.json")
    tuning.invalidate_cache()

    result = schema.BenchResult(name="autotune", suite=suite,
                                records=records, environment=env)
    bench_path = schema.save(result, out_dir)
    log(f"autotune: {len(records)} sweeps -> {tuning_path} + {bench_path}")
    return {"tuning_path": tuning_path, "bench_path": bench_path,
            "records": records}
