"""Benchmark subsystem: registry resolution, artifact schema round-trip,
RESULTS.md golden snippets, and an end-to-end smoke run of the paper
pipeline at its smallest grid."""

import json

import numpy as np
import pytest

from repro.bench import registry, report, runner, schema
from repro.bench.cases import check_monotone, check_rd_monotone
from repro.bench.timer import TimerConfig, Timing, measure

PAPER_TABLE_CASES = ("table1_lena", "table2_cablecar", "table3_psnr_lena",
                     "table4_psnr_cablecar")


# ---------------------------------------------------------------------------
# Registry resolution
# ---------------------------------------------------------------------------

def test_registry_has_paper_tables_and_serve_cases():
    cases = registry.all_cases()
    for name in PAPER_TABLE_CASES + ("rate_distortion",
                                     "entropy_throughput",
                                     "serve_batch_throughput",
                                     "serve_ragged", "framework_micro",
                                     "roofline"):
        assert name in cases
    # each paper table declares which table it feeds
    assert cases["table1_lena"].table == "Table 1"
    assert cases["table4_psnr_cablecar"].table == "Table 4"


@pytest.mark.parametrize("suite", ("smoke", "paper", "full"))
def test_suites_contain_all_paper_tables(suite):
    names = {c.name for c in registry.resolve(suite)}
    assert set(PAPER_TABLE_CASES) <= names


def test_smoke_excludes_micro_and_micro_excludes_tables():
    assert "framework_micro" not in {
        c.name for c in registry.resolve("smoke")}
    assert {c.name for c in registry.resolve("micro")} == {"framework_micro"}


def test_resolve_unknown_suite_and_case():
    with pytest.raises(KeyError):
        registry.resolve("nope")
    with pytest.raises(KeyError):
        registry.get("not_a_benchmark")
    with pytest.raises(KeyError):
        registry.resolve("smoke", names=["framework_micro"])  # not a member


def test_name_filter_preserves_request_order():
    picked = registry.resolve("paper", names=["table2_cablecar",
                                              "table1_lena"])
    assert [c.name for c in picked] == ["table2_cablecar", "table1_lena"]


def test_duplicate_registration_rejected():
    registry.all_cases()        # ensure cases.py has self-registered
    with pytest.raises(ValueError):
        registry.benchmark("table1_lena")(lambda ctx: [])
    with pytest.raises(ValueError):
        registry.benchmark("x", suites=("paper", "bogus"))


# ---------------------------------------------------------------------------
# Timer
# ---------------------------------------------------------------------------

def test_measure_counts_calls_and_blocks():
    calls = []
    t = measure(lambda: calls.append(1), warmup=2, iters=3)
    assert len(calls) == 5
    assert isinstance(t, Timing) and t.iters == 3
    assert t.best_us <= t.median_us


def test_timer_config_scaled():
    base = TimerConfig(warmup=2, iters=5)
    assert base.scaled(iters=1) == TimerConfig(2, 1)
    assert base.scaled() == base


# ---------------------------------------------------------------------------
# Artifact schema round-trip
# ---------------------------------------------------------------------------

def _fake_result(name="table1_lena", suite="paper"):
    rec = schema.BenchRecord(
        label="lena_512x512",
        params={"height": 512, "width": 512, "image": "lena",
                "transform": "exact", "quality": 50},
        timings_us={"parallel": {"median_us": 3902.7, "best_us": 3800.1,
                                 "iters": 3},
                    "serial": {"median_us": 28865.0, "best_us": 28001.5,
                               "iters": 3}},
        metrics={"speedup": 7.4, "mpix_per_s": 67.2})
    return schema.BenchResult(
        name=name, suite=suite, records=[rec],
        environment={"backend": "cpu", "device_count": 1,
                     "jax_version": "0", "git_sha": "abc1234",
                     "timestamp_utc": "2026-07-30T00:00:00Z"})


def test_schema_write_load_roundtrip(tmp_path):
    result = _fake_result()
    path = schema.save(result, tmp_path)
    assert path == tmp_path / "table1_lena.json"
    loaded = schema.load(path)
    assert loaded.to_json() == result.to_json()
    # and the round-tripped artifact still renders
    assert "Table 1" in report.render([loaded])


def test_schema_version_mismatch_rejected(tmp_path):
    blob = _fake_result().to_json()
    blob["schema_version"] = 999
    p = tmp_path / "old.json"
    p.write_text(json.dumps(blob))
    with pytest.raises(ValueError, match="schema_version"):
        schema.load(p)


def test_load_many_sorts_by_name(tmp_path):
    for name in ("zzz_case", "aaa_case"):
        schema.save(_fake_result(name=name), tmp_path)
    names = [r.name for r in schema.load_many(
        sorted(tmp_path.glob("*.json")))]
    assert names == ["aaa_case", "zzz_case"]


# ---------------------------------------------------------------------------
# RESULTS.md rendering (golden snippets)
# ---------------------------------------------------------------------------

def test_render_golden_snippet_timing_table():
    md = report.render([_fake_result()])
    assert "## Table 1 — DCT codec time vs Lena image size" in md
    # 28865.0us -> 28.865ms, 3902.7us -> 3.903ms
    assert "| lena | 512x512 | 28.865 | 3.903 | 7.4x | 67.2 |" in md
    assert "backend=`cpu`" in md and "git=`abc1234`" in md


def test_render_golden_snippet_psnr_table():
    rec = schema.BenchRecord(
        label="cablecar_320x288",
        params={"height": 320, "width": 288, "image": "cablecar",
                "quality": 50},
        metrics={"psnr_db_exact": 33.682, "psnr_db_cordic": 31.2,
                 "gap_db": 2.482})
    result = schema.BenchResult(name="table4_psnr_cablecar", suite="paper",
                                records=[rec], environment={})
    md = report.render([result])
    assert "## Table 4 — PSNR, exact DCT vs Cordic-Loeffler (Cable-car)" \
        in md
    assert "| cablecar | 320x288 | 33.682 | 31.200 | 2.482 |" in md


def test_render_empty_rejected_and_unknown_listed():
    with pytest.raises(ValueError):
        report.render([])
    odd = schema.BenchResult(name="mystery", suite="paper",
                             records=[], environment={})
    assert "`mystery`" in report.render([odd])


def test_timing_legs_handle_non_block_aligned_sizes():
    # the paper's full Lena grid includes 1024x814 (not divisible by 8);
    # both legs must pad rather than crash
    from repro.bench.cases import _timing_records
    recs = _timing_records(
        [(40, 26)], lambda h, w: np.zeros((h, w), "uint8"), "lena",
        registry.RunContext(suite="full", timer=TimerConfig(0, 1)))
    assert recs[0].label == "lena_40x26"
    assert recs[0].metrics["speedup"] > 0


def test_check_monotone():
    assert check_monotone({1: 10.0, 2: 20.0, 4: 30.0, 128: 1.0}) == []
    assert check_monotone({1: 10.0, 2: 5.0, 4: 30.0}) == [(1, 2)]


def test_render_golden_snippet_rd_table():
    rec = schema.BenchRecord(
        label="lena_200x200_q50",
        params={"height": 200, "width": 200, "image": "lena",
                "quality": 50, "transform": "exact", "nbytes": 2041},
        timings_us={"encode": {"median_us": 12000.0, "best_us": 11000.0,
                               "iters": 3},
                    "decode": {"median_us": 9000.0, "best_us": 8000.0,
                               "iters": 3}},
        metrics={"bpp": 0.4082, "compression_ratio": 19.6,
                 "psnr_db": 37.598, "enc_mpix_per_s": 3.3,
                 "dec_mpix_per_s": 4.4})
    md = report.render([schema.BenchResult(
        name="rate_distortion", suite="paper", records=[rec],
        environment={})])
    assert "## Rate–distortion (measured bytes)" in md
    assert "| lena | 200x200 | 50 | 0.408 | 19.6x | 37.60 " \
           "| 12.000 | 9.000 |" in md


def test_render_golden_snippet_entropy_table():
    stage = schema.BenchRecord(
        label="entropy_stage_256",
        params={"height": 256, "width": 256, "image": "lena",
                "quality": 50, "n_blocks": 1024, "payload_nbytes": 2786},
        timings_us={"enc_vectorized": {"median_us": 2000.0,
                                       "best_us": 1900.0, "iters": 5},
                    "enc_reference": {"median_us": 18000.0,
                                      "best_us": 17000.0, "iters": 2},
                    "dec_vectorized": {"median_us": 8000.0,
                                       "best_us": 7000.0, "iters": 5},
                    "dec_reference": {"median_us": 40000.0,
                                      "best_us": 39000.0, "iters": 2}},
        metrics={"enc_speedup": 9.0, "dec_speedup": 5.0,
                 "enc_mb_per_s": 32.8, "dec_mb_per_s": 8.2})
    batch = schema.BenchRecord(
        label="batch_8",
        params={"batch": 8, "height": 256, "width": 256, "quality": 50,
                "nbytes": 22288},
        timings_us={"encode_pipelined": {"median_us": 20000.0,
                                         "best_us": 19000.0, "iters": 5},
                    "decode_pipelined": {"median_us": 50000.0,
                                         "best_us": 49000.0, "iters": 5}},
        metrics={"enc_img_per_s": 400.0, "dec_img_per_s": 160.0,
                 "enc_mb_per_s": 26.2, "speedup_vs_reference": 7.5})
    md = report.render([schema.BenchResult(
        name="entropy_throughput", suite="paper", records=[stage, batch],
        environment={})])
    assert "## Entropy throughput (vectorized host coding)" in md
    assert "| encode | 2.000 | 18.000 | 9.0x | 32.8 |" in md
    assert "| 8 | 400.0 | 160.0 | 26.2 | 7.50x |" in md


def test_entropy_identity_gate_and_adversarial_blocks():
    from repro.bench.cases import (adversarial_blocks,
                                   entropy_identity_violations)
    # the gate must pass on the shipped implementation ...
    assert entropy_identity_violations(trials=3) == []
    # ... and its adversarial set must cover the documented corners:
    # a ZRL chain (zero run >= 16), an all-zero block, max amplitudes
    blocks = adversarial_blocks()
    assert any((ac == 0).all() for _, ac in blocks)
    assert any(np.abs(ac).max() == 32767 for _, ac in blocks)
    longest_run = 0
    for _, ac in blocks:
        for row in ac:
            nz = np.nonzero(row)[0]
            if nz.size:
                longest_run = max(longest_run, int(nz[0]))
    assert longest_run >= 16


def test_render_golden_snippet_tuning_table():
    rec = schema.BenchRecord(
        label="dct8x8_b256",
        params={"kernel": "dct8x8", "bucket": 256, "tile": 128,
                "candidates": [64, 128, 256]},
        timings_us={"tile_64": {"median_us": 900.0, "best_us": 880.0,
                                "iters": 3},
                    "tile_128": {"median_us": 500.0, "best_us": 480.0,
                                 "iters": 3},
                    "tile_256": {"median_us": 700.0, "best_us": 690.0,
                                 "iters": 3}},
        metrics={"best_us": 500.0, "speedup_vs_default": 1.4})
    md = report.render([schema.BenchResult(
        name="autotune", suite="paper", records=[rec],
        environment={"backend": "cpu"})])
    assert "## Kernel tile autotuning" in md
    assert "| dct8x8 | 256 | tile=128 | 0.500 | 1.40x | 3 |" in md


def test_render_golden_snippet_roofline_table():
    rec = schema.BenchRecord(
        label="dct8x8",
        params={"kernel": "dct8x8", "height": 256, "width": 256},
        timings_us={"routed": {"median_us": 2000.0, "best_us": 1900.0,
                               "iters": 3}},
        metrics={"flops": 2.1e6, "bytes_accessed": 2.6e6,
                 "achieved_gflop_s": 1.05, "achieved_gb_s": 1.31,
                 "frac_peak_flops": 5.33e-6, "frac_peak_bw": 1.6e-3,
                 "intensity_flop_per_byte": 0.81, "compute_bound": 0.0})
    bits = schema.BenchRecord(
        label="pack_bits",
        params={"kernel": "pack_bits", "payload_bits": 32768,
                "entropy_size": 128, "fields": 4000},
        timings_us={"routed": {"median_us": 800.0, "best_us": 790.0,
                               "iters": 3}},
        metrics={"flops": 0.0, "bytes_accessed": 52096.0,
                 "achieved_gflop_s": 0.0, "achieved_gb_s": 0.065,
                 "frac_peak_flops": 0.0, "frac_peak_bw": 7.9e-5,
                 "intensity_flop_per_byte": 0.0, "compute_bound": 0.0})
    md = report.render([schema.BenchResult(
        name="roofline", suite="paper", records=[rec, bits],
        environment={})])
    assert "## Kernel roofline (achieved vs peak)" in md
    assert "| dct8x8 | 256x256 | 2.000 | 1.05 | 1.31 " in md
    assert "| pack_bits | 32768 bits | 0.800 | 0.00 | 0.07 " in md
    assert "| memory |" in md


def test_default_artifacts_excludes_tuning_json(tmp_path):
    schema.save(_fake_result(), tmp_path)
    (tmp_path / "tuning.json").write_text("{}")
    paths = runner.default_artifacts(tmp_path)
    assert [p.name for p in paths] == ["table1_lena.json"]
    # ... so a report glob over a tuned results/ tree never crashes
    assert "Table 1" in report.render(schema.load_many(paths))


def test_autotune_sweep_machinery():
    """The sweep->entries->artifact pipeline on a fake candidate runner
    (no kernel timing): winner selection, record layout, tuning schema."""
    from repro.bench import autotune
    from repro.bench.timer import TimerConfig
    from repro.kernels import tuning

    fake_us = {8: 300.0, 16: 100.0, 32: 200.0}
    calls = []

    def run_candidate(tile):
        calls.append(tile)

    import repro.bench.timer as timer_mod
    real_measure = autotune.measure
    try:
        autotune.measure = lambda fn, cand, warmup, iters: (
            fn(cand) or timer_mod.Timing(median_us=fake_us[cand],
                                         best_us=fake_us[cand], iters=iters))
        rec = autotune._sweep_one(
            "dct8x8", 64, (8, 16, 32), run_candidate,
            TimerConfig(warmup=1, iters=2), lambda *_: None,
            extra_params={"image_hw": 64})
    finally:
        autotune.measure = real_measure

    assert calls == [8, 16, 32]
    assert rec.params["tile"] == 16 and rec.metrics["best_us"] == 100.0
    assert set(rec.timings_us) == {"tile_8", "tile_16", "tile_32"}
    entries = autotune.tuning_entries([rec])
    doc = tuning.make_doc(entries, backend="cpu")
    assert tuning.validate(doc)[0] == {
        "kernel": "dct8x8", "bucket": 64, "params": {"tile": 16},
        "best_us": 100.0}


def test_cli_has_autotune_subcommand():
    from repro.bench import cli
    args = cli.build_parser().parse_args(
        ["autotune", "--smoke", "--out", "r/"])
    assert args.fn is cli._cmd_autotune
    assert args.smoke and args.out == "r/"


def test_check_rd_monotone():
    good = [(10, 0.1, 30.0), (50, 0.4, 37.0), (90, 1.5, 40.0)]
    assert check_rd_monotone(good) == []
    # out-of-order input is sorted by quality before checking
    assert check_rd_monotone(list(reversed(good))) == []
    bad = [(10, 0.5, 30.0), (50, 0.4, 29.0)]
    assert check_rd_monotone(bad) == [("bpp", 10, 50), ("psnr", 10, 50)]


# ---------------------------------------------------------------------------
# End-to-end: smoke run of the paper pipeline at its smallest grid
# ---------------------------------------------------------------------------

def test_smoke_suite_end_to_end(tmp_path):
    out = tmp_path / "results"
    paths = runner.run_suite("smoke", out_dir=out, log=lambda *_: None)
    assert {p.name for p in paths} >= {f"{n}.json"
                                       for n in PAPER_TABLE_CASES}
    results = schema.load_many(paths)
    for r in results:
        assert r.suite == "smoke"
        assert r.records, f"{r.name} produced no records"
        assert r.environment["device_count"] >= 1

    md_path = report.write_results(results, tmp_path / "RESULTS.md")
    md = md_path.read_text()
    for title in ("## Table 1", "## Table 2", "## Table 3", "## Table 4",
                  "## Rate–distortion (measured bytes)",
                  "## Entropy throughput (vectorized host coding)",
                  "## Batch throughput", "## Ragged mixed-size batches",
                  "## Kernel roofline (achieved vs peak)"):
        assert title in md, f"missing section {title}"
    # sanity on reproduced physics: PSNR gap is positive (exact > cordic)
    t3 = next(r for r in results if r.name == "table3_psnr_lena")
    assert t3.records[0].metrics["gap_db"] > 0


def test_cli_report_from_artifacts(tmp_path, capsys):
    from repro.bench import cli
    schema.save(_fake_result(), tmp_path)
    md = tmp_path / "R.md"
    rc = cli.main(["report", str(tmp_path / "table1_lena.json"),
                   "--md", str(md)])
    assert rc == 0 and "Table 1" in md.read_text()
    rc = cli.main(["report", "--results-dir", str(tmp_path / "empty")])
    assert rc == 1
