"""Configurations, traffic mixes and per-layer metrics are files found
by the names in BENCHMARK.json: a new one of each is added by adding
files and entries, with no existing file edited."""

import json
import os
import pathlib
import shutil
import subprocess
import sys

from perfbench import run
from perfbench.tests.small import execute

ROOT = pathlib.Path(run.__file__).resolve().parents[1]


def _copy_bench(tmp: pathlib.Path) -> pathlib.Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "perfbench", tmp / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp


def test_new_config_mix_and_metric_are_found(tmp_path):
    root = _copy_bench(tmp_path)
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    bench = json.loads((root / "BENCHMARK.json").read_text())
    (root / "perfbench/configs/tiny.json").write_text(json.dumps({
        "images": [{"generator": "lena_like", "height": 32, "width": 48}],
        "quality": 40, "transforms": ["exact"]}))
    (root / "perfbench/traffic/codec_tiny.json").write_text(json.dumps({
        "kind": "codec_loop", "pixels_per_step": 3 * 32 * 48,
        "transform": "exact", "warm_steps": 1}))
    (root / "perfbench/limits/tiny.codec.json").write_text(json.dumps({
        "undecodable": 0, "header_mismatch": 0, "level_gap": 1e-3,
        "pixel_gap": 1e-3}))
    (root / "perfbench/metrics/demo.images_per_step.py").write_text(
        "def read(ctx):\n"
        "    return float(ctx.driver.batch)\n")
    bench["configs"].append({"name": "tiny", "source": "a test",
                             "file": "perfbench/configs/tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny.codec", "config": "tiny",
                               "traffic": "codec_tiny", "chips": 1,
                               "why": "a test"})
    bench["per_layer"].append({
        "name": "demo.images_per_step", "unit": "images", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "encode_mpix_s", "workloads": ["tiny.codec"]})
    for m in bench["end_to_end"]:
        if m["name"] == "encode_mpix_s":
            m["workloads"].append("tiny.codec")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = run.Cell("tiny.codec", root=root)
    assert cell.config["quality"] == 40
    assert [m["name"] for m in cell.per_layer] == ["demo.images_per_step"]
    res = execute(cell, seconds=0.5, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["demo.images_per_step"]["value"] == 3.0
    for p, data in before.items():
        assert p.read_bytes() == data, f"{p} was edited"


def _run(cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "thumb256.codec",
         "--seed", "1", "--seconds", "1"], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=120)


def test_no_chip_means_no_result():
    p = _run(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_bare_benchmark_without_the_program_fails(tmp_path):
    root = _copy_bench(tmp_path)
    p = _run(root, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not p.stdout.strip()
