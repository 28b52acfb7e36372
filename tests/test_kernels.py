"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cordic, images, metrics, quant
from repro.core.entropy import bitio
from repro.kernels import grad_dct, pack_bits
from repro.kernels.cordic_loeffler import (cordic_loeffler_dct,
                                           cordic_loeffler_idct,
                                           cordic_loeffler_ref)
from repro.kernels.dct8x8 import dct8x8, dct8x8_ref, idct8x8, idct8x8_ref
from repro.kernels.fused_codec import fused_codec, fused_codec_ref

SHAPES = [(8, 8), (16, 64), (64, 16), (128, 128), (96, 200), (120, 104)]


def _img(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.default_rng(seed).normal(
        scale=50, size=shape).astype(dtype))


class TestDct8x8Kernel:
    @pytest.mark.parametrize("shape", SHAPES)
    def test_forward_matches_ref(self, shape):
        x = _img(shape)
        np.testing.assert_allclose(np.asarray(dct8x8(x)),
                                   np.asarray(dct8x8_ref(x)),
                                   atol=2e-3)

    @pytest.mark.parametrize("shape", [(16, 16), (64, 128)])
    def test_inverse_roundtrip(self, shape):
        x = _img(shape, 1)
        rec = idct8x8(dct8x8(x))
        np.testing.assert_allclose(np.asarray(rec), np.asarray(x), atol=1e-2)

    def test_batched(self):
        x = _img((3, 32, 32), 2)
        out = dct8x8(x)
        for i in range(3):
            np.testing.assert_allclose(np.asarray(out[i]),
                                       np.asarray(dct8x8_ref(x[i])),
                                       atol=2e-3)

    def test_bfloat16(self):
        x = _img((64, 64), 3).astype(jnp.bfloat16)
        out = dct8x8(x)
        assert out.dtype == jnp.bfloat16
        ref = dct8x8_ref(x.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.06, atol=2.0)

    @pytest.mark.parametrize("tile", [8, 64, 256])
    def test_tile_sizes_agree(self, tile):
        x = _img((128, 128), 4)
        np.testing.assert_allclose(np.asarray(dct8x8(x, tile=tile)),
                                   np.asarray(dct8x8(x, tile=128)),
                                   atol=1e-4)


class TestCordicLoefflerKernel:
    @pytest.mark.parametrize("shape", SHAPES[:4])
    def test_matches_ref_exactly(self, shape):
        x = _img(shape, 5)
        out = cordic_loeffler_dct(x)
        ref = cordic_loeffler_ref(x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=0)

    def test_inverse_matches_ref(self, rng=None):
        x = _img((32, 32), 6)
        coeffs = cordic_loeffler_dct(x)
        rec = cordic_loeffler_idct(coeffs)
        ref = cordic_loeffler_ref(np.asarray(coeffs), inverse=True)
        np.testing.assert_allclose(np.asarray(rec), np.asarray(ref), atol=0)

    def test_float_config_approximates_exact(self):
        cfg = cordic.CordicConfig(16, 16, None)
        x = _img((32, 32), 7)
        out = cordic_loeffler_dct(x, config=cfg)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(dct8x8_ref(x)), atol=0.05)


class TestFusedCodecKernel:
    @pytest.mark.parametrize("quality", [10, 50, 90])
    def test_matches_unfused_ref(self, quality):
        img = images.lena_like(64, 64)
        rec, qc = fused_codec(img, quality=quality)
        ref_rec, ref_qc = fused_codec_ref(
            jnp.asarray(img, jnp.float32), quality)
        # kron-matmul vs separable accumulation order: allow off-by-one
        # quant levels at round boundaries for a tiny fraction of coeffs
        diff = np.abs(np.asarray(qc) - np.asarray(ref_qc))
        assert diff.max() <= 1
        assert (diff > 0).mean() < 1e-3
        np.testing.assert_allclose(np.asarray(rec, np.float32),
                                   np.asarray(ref_rec), atol=3.0)

    def test_cordic_transform_mode(self):
        img = images.cablecar_like(64, 64)
        rec, qc = fused_codec(img, quality=50, transform="cordic")
        ref_rec, ref_qc = fused_codec_ref(jnp.asarray(img, jnp.float32), 50,
                                          transform="cordic")
        assert (np.asarray(qc) == np.asarray(ref_qc)).all()

    def test_psnr_sane(self):
        img = images.lena_like(128, 128)
        rec, _ = fused_codec(img, quality=50)
        assert float(metrics.psnr(jnp.asarray(img), rec)) > 28.0


class TestPackBitsKernel:
    """Routed entropy bit packing: the staged NumPy reference and the
    Pallas scatter-pack kernel must be byte-identical to the retained
    ``bitio.pack_bits`` host-edge reference on every input."""

    @staticmethod
    def _both(codes, lengths):
        codes = np.asarray(codes)
        lengths = np.asarray(lengths)
        want = bitio.pack_bits(codes, lengths)
        assert pack_bits.pack_bits_ref(codes, lengths) == want
        assert pack_bits.pack_bits(codes, lengths, backend="pallas",
                                   interpret=True) == want
        return want

    @given(st.integers(0, 2**31 - 1))
    @settings(max_examples=12, deadline=None)
    def test_random_field_streams(self, seed):
        rng = np.random.default_rng(seed)
        m = int(rng.integers(1, 700))
        # widths 0..16 with zero-width (absent amplitude) slots included
        lengths = rng.integers(0, 17, m)
        codes = rng.integers(0, 1 << 16, m) & ((1 << np.maximum(
            lengths, 1)) - 1)
        self._both(codes, lengths)

    @pytest.mark.parametrize("codes,lengths", [
        ([], []),                            # empty stream
        ([0], [0]),                          # only zero-width fields
        ([1], [1]),                          # single bit
        ([0xFFFF], [16]),                    # one max-width field
        ([0b101, 0b1], [3, 1]),              # partial final byte
        ([0xFFFF] * 200, [16] * 200),        # all-ones across 4 tiles
        ([0] * 1500, [1] * 1500),            # worst-case window density
        ([5, 0, 3, 0, 7], [3, 0, 2, 0, 3]),  # interleaved zero-widths
    ])
    def test_edge_cases(self, codes, lengths):
        self._both(codes, lengths)

    def test_tile_boundary_straddles(self):
        # 16-bit fields at every alignment force codes to straddle the
        # 1024-bit tile boundary in all 8 phase positions
        for phase in range(8):
            lengths = [1] * phase + [16] * 200
            codes = [1] * phase + [0xABCD & 0xFFFF] * 200
            self._both(codes, lengths)

    def test_multi_tile_payload(self):
        rng = np.random.default_rng(0)
        m = 4000                             # ~32k bits, many tiles
        lengths = rng.integers(1, 17, m)
        codes = rng.integers(0, 1 << 16, m) & ((1 << lengths) - 1)
        self._both(codes, lengths)

    def test_high_bits_above_field_width_are_ignored(self):
        # the contract reads only the low `lengths[k]` bits; stray high
        # bits must not leak into neighbouring bytes on any backend
        self._both([1, 3], [1, 1])
        self._both([0xFFFF, 0xFFFF, 0x7FFF], [3, 16, 1])
        rng = np.random.default_rng(7)
        lengths = rng.integers(0, 17, 300)
        codes = rng.integers(0, 1 << 16, 300)      # deliberately unmasked
        self._both(codes, lengths)

    def test_width_over_16_rejected(self):
        with pytest.raises(ValueError, match="wider"):
            pack_bits.pack_bits_ref(np.array([1]), np.array([17]))
        with pytest.raises(ValueError, match="wider"):
            pack_bits.pack_bits(np.array([1]), np.array([17]),
                                backend="pallas", interpret=True)

    def test_oversize_stream_falls_back_to_reference(self, monkeypatch):
        # streams past the VMEM guard must quietly take the NumPy path
        from repro.kernels.pack_bits import ops
        monkeypatch.setattr(ops, "MAX_DEVICE_FIELDS", 64)
        rng = np.random.default_rng(11)
        lengths = rng.integers(0, 17, 300)
        codes = rng.integers(0, 1 << 16, 300)
        self._both(codes, lengths)

    def test_backend_selection(self, pallas_route):
        # off-TPU "auto" resolves to the NumPy reference, and the encode
        # keeps its default packer; pinned, it is the device pack
        assert pack_bits.select_backend("auto") in pack_bits.BACKENDS
        if jax.default_backend() != "tpu":
            assert pack_bits.select_backend("auto") == "numpy"
            assert pack_bits.make_packer() is None
        pallas_route("pack")
        assert pack_bits.make_packer() is not None


@pytest.mark.parametrize("kernel", ["pack_bits", "symbolize",
                                    "unpack_bits"])
def test_select_backend_rejects_unknown_name(kernel):
    import importlib
    ops = importlib.import_module(f"repro.kernels.{kernel}.ops")
    with pytest.raises(ValueError, match=f"unknown {kernel} backend"):
        ops.select_backend("cuda")


class TestGradDctKernel:
    def test_encode_decode_match_ref(self):
        g = _img((8192,), 8)
        cg = grad_dct.encode(g, keep=16)
        q_ref, s_ref = grad_dct.grad_dct_encode_ref(g.reshape(-1, 64), 16)
        assert (np.asarray(cg.q) == np.asarray(q_ref)).all()
        np.testing.assert_allclose(np.asarray(cg.scale), np.asarray(s_ref),
                                   rtol=1e-6)
        dec = grad_dct.decode(cg)
        ref = grad_dct.grad_dct_decode_ref(q_ref, s_ref).reshape(-1)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(ref),
                                   atol=1e-5)

    @given(st.integers(1, 500), st.sampled_from([8, 16, 32, 48]))
    @settings(max_examples=20, deadline=None)
    def test_arbitrary_lengths(self, n, keep):
        g = _img((n,), n)
        dec = grad_dct.decode(grad_dct.encode(g, keep=keep))
        assert dec.shape == g.shape
        tail = n % 64
        if tail:
            np.testing.assert_allclose(np.asarray(dec[-tail:]),
                                       np.asarray(g[-tail:]))

    def test_smooth_signal_compacts(self):
        # low-frequency signal: keep=16 of 64 should reconstruct well
        t = np.linspace(0, 4 * np.pi, 4096).astype(np.float32)
        g = jnp.asarray(np.sin(t) + 0.5 * np.cos(2 * t))
        dec = grad_dct.decode(grad_dct.encode(g, keep=16))
        rel = float(jnp.linalg.norm(dec - g) / jnp.linalg.norm(g))
        assert rel < 0.05

    def test_wire_bytes_ratio(self):
        g = _img((65536,), 9)
        cg = grad_dct.encode(g, keep=16)
        ratio = g.size * 4 / cg.wire_bytes()
        assert ratio > 10.0  # 256/(16+4) = 12.8x nominal

    def test_keep_64_is_near_lossless_modulo_quant(self):
        g = _img((4096,), 10)
        dec = grad_dct.decode(grad_dct.encode(g, keep=64))
        rel = float(jnp.linalg.norm(dec - g) / jnp.linalg.norm(g))
        assert rel < 0.01  # int8 quantisation only
