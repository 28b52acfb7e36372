"""Tuned-tile artifact robustness: schema round-trip, fallback-with-one-
warning on every failure mode (missing / corrupt / wrong version /
backend mismatch), bucket precedence, and the ops.py routing seam."""

import json
import threading
import warnings

import numpy as np
import pytest

from repro.kernels import tuning


@pytest.fixture
def tuning_path(tmp_path, monkeypatch):
    """Point the loader at a per-test artifact path, cache cleared on
    both sides so no test sees another's artifact or warning history."""
    path = tmp_path / "tuning.json"
    monkeypatch.setenv(tuning.ENV_VAR, str(path))
    tuning.invalidate_cache()
    yield path
    tuning.invalidate_cache()


def _entry(kernel="dct8x8", bucket=64, value=32):
    return {"kernel": kernel, "bucket": bucket,
            "params": {tuning.PARAM_OF[kernel]: value}, "best_us": 123.0}


def _write(path, entries, backend="cpu", **doc_overrides):
    doc = tuning.make_doc(entries, backend=backend,
                          environment={"git_sha": "abc1234"})
    doc.update(doc_overrides)
    path.write_text(json.dumps(doc))


def _no_warnings(fn):
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out = fn()
    assert [str(x.message) for x in w] == []
    return out


# ---------------------------------------------------------------------------
# Schema round-trip + validation
# ---------------------------------------------------------------------------

def test_artifact_roundtrip(tuning_path):
    entries = [_entry("dct8x8", 64, 32), _entry("pack_bits", 4096, 512)]
    written = tuning.save(tuning.make_doc(entries, backend="cpu"),
                          tuning_path)
    assert written == tuning_path
    assert tuning.validate(json.loads(tuning_path.read_text())) == entries
    assert tuning.lookup("dct8x8", 64, backend="cpu") == {"tile": 32}
    assert tuning.lookup("pack_bits", 4000, backend="cpu") == {
        "tile_bits": 512}


@pytest.mark.parametrize("mutate, msg", [
    (lambda d: d.update(schema_version=999), "schema_version"),
    (lambda d: d.pop("backend"), "backend"),
    (lambda d: d.update(entries="nope"), "entries"),
    (lambda d: d["entries"].append({"kernel": "warp_drive", "bucket": 64,
                                    "params": {"tile": 32}}),
     "unknown kernel"),
    (lambda d: d["entries"].append(_entry(bucket=48)), "pow2"),
    (lambda d: d["entries"].append(_entry(value=48)), "pow2"),
    (lambda d: d["entries"].append({"kernel": "dct8x8", "bucket": 64,
                                    "params": {}}), "lacks param"),
])
def test_validate_rejects(mutate, msg):
    doc = tuning.make_doc([_entry()], backend="cpu")
    mutate(doc)
    with pytest.raises(ValueError, match=msg):
        tuning.validate(doc)


def test_bucket_of_pow2_ceiling():
    assert tuning.bucket_of(1) == 8
    assert tuning.bucket_of(8) == 8
    assert tuning.bucket_of(9) == 16
    assert tuning.bucket_of(256) == 256
    assert tuning.bucket_of(257) == 512


# ---------------------------------------------------------------------------
# Fallback-with-one-warning on every failure mode
# ---------------------------------------------------------------------------

def _assert_single_warning_then_silence(match):
    with pytest.warns(tuning.TuningWarning, match=match):
        assert tuning.lookup("dct8x8", 64, backend="cpu") is None
    # the second lookup must be silent (one warning per failure reason)
    assert _no_warnings(
        lambda: tuning.lookup("dct8x8", 64, backend="cpu")) is None
    # and tile_for falls back to the built-in default
    assert tuning.tile_for("dct8x8", 64, backend="cpu") == \
        tuning.DEFAULTS["dct8x8"]["tile"]


def test_missing_file_falls_back(tuning_path):
    _assert_single_warning_then_silence("no tuning artifact")


def test_corrupt_json_falls_back(tuning_path):
    tuning_path.write_text("{not json!")
    _assert_single_warning_then_silence("rejected")


def test_wrong_schema_version_falls_back(tuning_path):
    _write(tuning_path, [_entry()], schema_version=999)
    _assert_single_warning_then_silence("schema_version")


def test_invalid_entries_fall_back(tuning_path):
    _write(tuning_path, [_entry()])
    doc = json.loads(tuning_path.read_text())
    doc["entries"][0]["bucket"] = 48
    tuning_path.write_text(json.dumps(doc))
    _assert_single_warning_then_silence("rejected")


def test_backend_mismatch_falls_back(tuning_path):
    _write(tuning_path, [_entry()], backend="tpu")
    _assert_single_warning_then_silence("backend")


def test_valid_artifact_loads_silently(tuning_path):
    _write(tuning_path, [_entry("dct8x8", 64, 16)])
    assert _no_warnings(
        lambda: tuning.lookup("dct8x8", 64, backend="cpu")) == {"tile": 16}
    assert tuning.tile_for("dct8x8", 64, backend="cpu") == 16


def test_unknown_kernel_lookup_raises(tuning_path):
    with pytest.raises(KeyError, match="unknown kernel"):
        tuning.lookup("warp_drive", 64, backend="cpu")


# ---------------------------------------------------------------------------
# Bucket precedence
# ---------------------------------------------------------------------------

def test_bucket_precedence_smallest_covering_else_largest(tuning_path):
    _write(tuning_path, [_entry("dct8x8", 64, 16),
                         _entry("dct8x8", 256, 128)])
    # exact bucket
    assert tuning.tile_for("dct8x8", 64, backend="cpu") == 16
    # dim 100 -> bucket 128: smallest swept bucket >= 128 is 256
    assert tuning.tile_for("dct8x8", 100, backend="cpu") == 128
    # below the smallest bucket: the 64 sweep covers it
    assert tuning.tile_for("dct8x8", 10, backend="cpu") == 16
    # beyond the largest bucket: nearest (largest) swept entry applies
    assert tuning.tile_for("dct8x8", 4096, backend="cpu") == 128
    # a kernel with no entries keeps its built-in default, silently
    assert _no_warnings(
        lambda: tuning.tile_for("unpack_bits", 4096, backend="cpu")) == \
        tuning.DEFAULTS["unpack_bits"]["tile_bits"]


def test_concurrent_lookups_consistent(tuning_path):
    _write(tuning_path, [_entry("dct8x8", 64, 32)])
    got, errs = [], []

    def hit():
        try:
            got.append(tuning.tile_for("dct8x8", 64, backend="cpu"))
        except Exception as e:  # pragma: no cover - failure path
            errs.append(e)

    threads = [threading.Thread(target=hit) for _ in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs and got == [32] * 16


# ---------------------------------------------------------------------------
# The ops.py routing seam: tile=None consults the artifact
# ---------------------------------------------------------------------------

def test_dct8x8_routes_tuned_tile(tuning_path, monkeypatch):
    from repro.kernels import common
    from repro.kernels.dct8x8 import kernel, ops
    _write(tuning_path, [_entry("dct8x8", 64, 16)])
    seen = {}
    real = kernel.dct8x8_pallas

    def spy(x, *, tile_h, tile_w, **kw):
        seen["tile"] = (tile_h, tile_w)
        return real(x, tile_h=tile_h, tile_w=tile_w, **kw)

    monkeypatch.setattr(kernel, "dct8x8_pallas", spy)
    x = np.zeros((64, 64), np.float32)
    ops.dct8x8(x)                       # tile=None -> tuned 16
    assert seen["tile"] == common.tile_shape(64, 64, 16) == (8, 64)
    ops.dct8x8(x, tile=32)              # explicit tile pins the knob
    assert seen["tile"] == common.tile_shape(64, 64, 32) == (16, 64)


def test_pack_bits_routes_tuned_tile_bits(tuning_path, monkeypatch):
    from repro.kernels.pack_bits import kernel, ops
    _write(tuning_path, [_entry("pack_bits", 8192, 256)])
    seen = {}
    real = kernel.pack_bits_pallas

    def spy(*args, tile_bits, window, **kw):
        seen["tb"] = (tile_bits, window)
        return real(*args, tile_bits=tile_bits, window=window, **kw)

    monkeypatch.setattr(kernel, "pack_bits_pallas", spy)
    rng = np.random.default_rng(0)
    lengths = rng.integers(1, 17, 300)
    codes = rng.integers(0, 1 << 16, 300) & ((1 << lengths) - 1)
    want = ops.pack_bits(codes, lengths, backend="numpy")
    got = ops.pack_bits(codes, lengths, backend="pallas", interpret=True)
    assert got == want
    assert seen["tb"] == (256, 256 + ops.WINDOW_MARGIN)
    # explicit tile_bits pins the knob
    ops.pack_bits(codes, lengths, backend="pallas", tile_bits=512,
                  interpret=True)
    assert seen["tb"] == (512, 512 + ops.WINDOW_MARGIN)


def test_committed_artifact_is_valid_for_routers():
    """The repo-root results/tuning.json (when present) must validate and
    carry an entry for every kernel, so the routers never warn in CI."""
    import pathlib
    path = pathlib.Path(tuning.__file__).resolve().parents[3] \
        / "results" / "tuning.json"
    if not path.exists():
        pytest.skip("no committed tuning artifact")
    doc = json.loads(path.read_text())
    entries = tuning.validate(doc)
    assert {e["kernel"] for e in entries} == set(tuning.KERNELS)
