"""Batched multi-device codec engine vs the single-image reference."""

import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from helpers.routes import reassemble_routes

from repro.core import codec, entropy, images, metrics
from repro.serve import codec_engine as eng

TRANSFORMS = ["exact", "loeffler", "cordic"]


def _batch(n=5, h=96, w=102):
    # non-8-divisible width: the paper's 1024x814 case, batched
    return np.stack([images.lena_like(h, w, seed=i) if i % 2 == 0
                     else images.cablecar_like(h, w, seed=i)
                     for i in range(n)])


class TestBatchVsLoop:
    @pytest.mark.parametrize("transform", TRANSFORMS)
    def test_roundtrip_matches_per_image_bitexact(self, transform):
        batch = _batch()
        rec, psnr = eng.roundtrip_batch(batch, 50, transform)
        assert rec.shape == batch.shape and rec.dtype == jnp.uint8
        for i in range(batch.shape[0]):
            ref, p = codec.roundtrip(batch[i], 50, transform)
            np.testing.assert_array_equal(np.asarray(rec[i]),
                                          np.asarray(ref))
            assert abs(psnr[i] - p) < 1e-4

    @pytest.mark.parametrize("transform", TRANSFORMS)
    def test_compress_matches_per_image_qcoeffs(self, transform):
        batch = _batch(n=3, h=64, w=64)
        cb = eng.compress_batch(batch, 50, transform)
        (grp,) = cb.groups
        for i in range(3):
            c = codec.compress(batch[i], 50, transform)
            np.testing.assert_array_equal(np.asarray(grp.qcoeffs[i]),
                                          np.asarray(c.qcoeffs))

    def test_matched_mode_matches_per_image(self):
        batch = _batch(n=3, h=64, w=64)
        cb = eng.compress_batch(batch, 50, "cordic")
        rec = eng.decompress_batch(cb, mode="matched")
        for i in range(3):
            ref = codec.decompress(codec.compress(batch[i], 50, "cordic"),
                                   mode="matched")
            np.testing.assert_array_equal(np.asarray(rec[i]),
                                          np.asarray(ref))

    def test_empty_batch_rejected_cleanly(self):
        with pytest.raises(ValueError, match="empty batch"):
            eng.compress_batch(np.zeros((0, 64, 64), np.uint8))
        with pytest.raises(ValueError, match="empty batch"):
            eng.compress_batch([])

    def test_non_power_of_two_batch_is_padded_and_cropped(self):
        batch = _batch(n=7, h=64, w=64)     # pads to 8 internally
        rec, psnr = eng.roundtrip_batch(batch, 50)
        assert rec.shape == (7, 64, 64)
        assert psnr.shape == (7,)


class TestRagged:
    def test_padding_roundtrip_mixed_sizes(self):
        rag = [images.lena_like(64, 64, seed=0),
               images.cablecar_like(100, 52, seed=1),
               images.lena_like(64, 64, seed=2),
               images.lena_like(200, 178, seed=3)]
        cb = eng.compress_batch(rag, 50)
        # equal buckets grouped: both 64x64 images share one group
        sizes = sorted(len(g.indices) for g in cb.groups)
        assert sizes == [1, 1, 2]
        rec = eng.decompress_batch(cb)
        assert [tuple(r.shape) for r in rec] == [
            (64, 64), (100, 52), (64, 64), (200, 178)]
        for im, r in zip(rag, rec):
            ref, _ = codec.roundtrip(im, 50)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))

    def test_bucketing_bounds_compiled_shapes(self):
        # 63/65/70-wide images all land in the same 64/128 buckets
        rag = [images.lena_like(64, 63, seed=0),
               images.lena_like(60, 65, seed=1),
               images.lena_like(58, 70, seed=2)]
        cb = eng.compress_batch(rag, 50)
        buckets = {(g.qcoeffs.shape[1] * 8, g.qcoeffs.shape[2] * 8)
                   for g in cb.groups}
        assert buckets == {(64, 64), (64, 128)}

    def test_ragged_roundtrip_psnr(self):
        rag = [images.lena_like(96, 96, seed=0),
               images.cablecar_like(120, 88, seed=1)]
        rec, psnr = eng.roundtrip_batch(rag, 50)
        assert len(rec) == 2 and psnr.shape == (2,)
        assert (psnr > 25.0).all()


class TestPsnrParity:
    def test_psnr_range_matches_paper_tables(self):
        # same expectations as tests/test_quant_codec.py, through the engine
        batch = np.stack([images.lena_like(512, 512)])
        _, p = eng.roundtrip_batch(batch, 50)
        assert 28.0 < p[0] < 45.0
        batch2 = np.stack([images.cablecar_like(320, 288)])
        _, p2 = eng.roundtrip_batch(batch2, 50)
        assert 24.0 < p2[0] < 42.0
        assert p2[0] < p[0]

    def test_quality_ordering_batched(self):
        batch = np.stack([images.lena_like(128, 128, seed=i)
                          for i in range(3)])
        psnrs = [eng.roundtrip_batch(batch, q)[1].mean()
                 for q in (10, 50, 90)]
        assert psnrs[0] < psnrs[1] < psnrs[2]

    def test_cordic_gap_in_paper_band_batched(self):
        batch = np.stack([images.lena_like(256, 256, seed=i)
                          for i in range(2)])
        _, pe = eng.roundtrip_batch(batch, 50, "exact")
        _, pc = eng.roundtrip_batch(batch, 50, "cordic")
        gap = pe.mean() - pc.mean()
        assert 0.5 < gap < 4.0, (pe, pc)


def _spy(monkeypatch, name: str) -> list:
    """Wrap the engine's program ``name``; the list collects its outputs."""
    real, outs = getattr(eng, name), []

    def spy(*args, **kwargs):
        outs.append(real(*args, **kwargs))
        return outs[-1]
    monkeypatch.setattr(eng, name, spy)
    return outs


def _assert_staged_per_image(imgs, rec, psnr, transform="exact",
                             mode="standard"):
    """Each reconstruction bit for bit as the single-image staged path
    gives it, and each PSNR as the engine scores that reference: one
    vmapped program over a stacked batch, per image for a list."""
    assert len(rec) == len(imgs) and psnr.shape == (len(imgs),)
    refs = [codec.roundtrip(im, 50, transform, mode=mode)[0] for im in imgs]
    for r, ref in zip(rec, refs):
        assert r.dtype == jnp.uint8
        np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))
    if isinstance(imgs, np.ndarray):
        want = eng._psnr_vec(jnp.asarray(imgs), jnp.stack(refs))
    else:
        want = [metrics.psnr(jnp.asarray(im), ref)
                for im, ref in zip(imgs, refs)]
    np.testing.assert_array_equal(psnr, np.asarray(want, np.float32))


class TestReassembleRoutes:
    """A stacked group leaves the engine as the program's own output or
    one crop of it; a ragged list one slice per image; a decode bucket
    of one size one crop program.  Outputs are unchanged, bit for bit."""

    @pytest.mark.parametrize("transform, mode",
                             [("exact", "standard"), ("cordic", "matched")])
    def test_stacked_aligned_batch_is_the_programs_output(
            self, monkeypatch, transform, mode):
        batch = _batch(n=4, h=64, w=48)
        outs = _spy(monkeypatch, "_decompress_sharded")
        (rec, psnr), routes = reassemble_routes(
            lambda: eng.roundtrip_batch(batch, 50, transform, mode=mode))
        assert routes == {"direct": 1}
        assert rec is outs[-1]
        _assert_staged_per_image(batch, rec, psnr, transform, mode)

    @pytest.mark.parametrize("n", [3, 4])
    def test_stacked_unaligned_batch_is_one_crop(self, n):
        batch = _batch(n=n, h=37, w=53)
        (rec, psnr), routes = reassemble_routes(
            lambda: eng.roundtrip_batch(batch, 50))
        assert routes == {"program": 1}
        assert rec.shape == batch.shape
        _assert_staged_per_image(batch, rec, psnr)

    def test_decompress_batch_of_a_stacked_input(self):
        batch = _batch(n=2, h=37, w=53)
        cb = eng.compress_batch(batch, 50)
        rec, routes = reassemble_routes(lambda: eng.decompress_batch(cb))
        assert routes == {"program": 1} and rec.shape == batch.shape
        for im, r in zip(batch, rec):
            ref, _ = codec.roundtrip(im, 50)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))

    def test_ragged_list_is_sliced_per_image_in_input_order(self):
        rag = [images.lena_like(64, 64, seed=0),
               images.cablecar_like(100, 52, seed=1),
               images.lena_like(37, 53, seed=2),
               images.lena_like(64, 64, seed=3)]
        (rec, psnr), routes = reassemble_routes(
            lambda: eng.roundtrip_batch(rag, 50))
        assert routes == {"per_image": 2}        # buckets 64x64, 128x64
        assert isinstance(rec, list)
        assert [r.shape for r in rec] == [im.shape for im in rag]
        _assert_staged_per_image(rag, rec, psnr)

    @pytest.mark.parametrize("h, w, route", [(64, 48, "direct"),
                                             (37, 53, "program")])
    def test_fused_roundtrip_returns_its_group(self, monkeypatch, h, w,
                                               route):
        # the TPU's fused route, its kernel interpreted on the CPU
        from repro.kernels.fused_codec import fused_codec
        monkeypatch.setattr(eng, "_fused_ok", lambda transform, mode: True)
        outs = _spy(monkeypatch, "_fused_roundtrip_sharded")
        batch = _batch(n=2, h=h, w=w)
        (rec, psnr), routes = reassemble_routes(
            lambda: eng.roundtrip_batch(batch, 50))
        assert routes == {route: 1}
        assert (rec is outs[-1]) == (route == "direct")
        assert rec.shape == batch.shape
        for im, r in zip(batch, rec):
            ref, _ = fused_codec(im, quality=50)
            np.testing.assert_array_equal(np.asarray(r), np.asarray(ref))
        want = eng._psnr_vec(jnp.asarray(batch), fused_codec(
            batch, quality=50)[0])
        np.testing.assert_array_equal(psnr, np.asarray(want))

    def test_decode_bucket_of_one_size_is_one_crop_program(self,
                                                           monkeypatch):
        imgs = [images.lena_like(37, 53, seed=i) for i in range(3)]
        blobs = eng.encode_batch(imgs, 50)
        crops = _spy(monkeypatch, "_crop_each")
        recs, routes = reassemble_routes(lambda: eng.decode_batch(blobs))
        assert routes == {"program": 1}
        (crop,) = crops
        assert all(r is c for r, c in zip(recs, crop))
        for blob, r in zip(blobs, recs):
            assert r.shape == (37, 53)
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(entropy.decode_image(blob)))

    def test_decode_bucket_of_mixed_sizes_is_sliced_per_image(
            self, monkeypatch):
        # 60x64 and 64x64 share the 8x8-block grid; 40x48 is a bucket of
        # one size
        imgs = [images.lena_like(60, 64, seed=0),
                images.lena_like(40, 48, seed=1),
                images.cablecar_like(64, 64, seed=2),
                images.lena_like(40, 48, seed=3)]
        blobs = eng.encode_batch(imgs, 50)
        crops = _spy(monkeypatch, "_crop_each")
        recs, routes = reassemble_routes(lambda: eng.decode_batch(blobs))
        assert routes == {"program": 1, "per_image": 1}
        assert [len(c) for c in crops] == [2]
        assert [r.shape for r in recs] == [im.shape for im in imgs]
        for blob, r in zip(blobs, recs):
            np.testing.assert_array_equal(
                np.asarray(r), np.asarray(entropy.decode_image(blob)))


@pytest.mark.multidevice
def test_sharded_engine_matches_per_image():
    """The shard_map path (8 emulated devices) stays bit-exact."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import numpy as np
from repro.core import codec, images
from repro.serve import codec_engine as eng
imgs = np.stack([images.lena_like(64, 64, seed=i) for i in range(6)])
rec, psnr = eng.roundtrip_batch(imgs, 50, 'cordic')
for i in range(6):
    ref, p = codec.roundtrip(imgs[i], 50, 'cordic')
    np.testing.assert_array_equal(np.asarray(rec[i]), np.asarray(ref))
    assert abs(psnr[i] - p) < 1e-4
print('TEST-OK')
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "TEST-OK" in r.stdout


@pytest.mark.multidevice
def test_sharded_decode_crops_its_group_with_one_program():
    """On 8 emulated devices a decode bucket arrives sharded; the crop
    program returns the same per-image arrays as the single image API."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = """
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import numpy as np
from repro import obs
from repro.core import entropy, images
from repro.serve import codec_engine as eng
imgs = [images.lena_like(37, 53, seed=i) for i in range(8)] + \
    [images.cablecar_like(64, 56, seed=i) for i in range(3)]
blobs = eng.encode_batch(imgs, 50)
real, seen = eng._crop_each, []
def spy(rec, h, w):
    seen.append(len(rec.sharding.device_set))
    return real(rec, h, w)
eng._crop_each = spy
before = obs.counts()
recs = eng.decode_batch(blobs)
assert seen == [8, 8], seen
assert obs.counts()['engine.reassemble.program'] - \
    before.get('engine.reassemble.program', 0) == 2
assert 'engine.reassemble.per_image' not in obs.counts()
for blob, im, r in zip(blobs, imgs, recs):
    assert r.shape == im.shape
    np.testing.assert_array_equal(np.asarray(r),
                                  np.asarray(entropy.decode_image(blob)))
print('TEST-OK')
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "TEST-OK" in r.stdout
