"""Entropy-coded bitstream stage: quantised blocks -> real bytes.

Completes the paper's pipeline (DCT -> quantise -> IDCT) with a
JPEG-style lossless entropy stage so compression ratios are *measured*
bytes, not the :func:`repro.core.quant.estimate_bits` proxy:

* :mod:`scan`      — zig-zag scan + DC differential, vectorised in JAX
  (vmappable per block; this half rides the accelerator),
* :mod:`rle`       — the run-length symbol alphabet, the scalar
  oracles and the LUT-walk decoder, NumPy at the host edge,
* :mod:`dense`     — the host symbolizer: one fused pass over a dense
  per-block slot layout, the container's default ``symbolizer``,
* :mod:`huffman`   — canonical, length-limited Huffman codes built from
  per-stream symbol frequencies, plus the shared-table registry
  (well-known ITU-T T.81 Annex K tables under ids >= 1),
* :mod:`bitio`     — MSB-first bit packing/unpacking (NumPy; the
  retained reference the routed :mod:`repro.kernels.pack_bits` backend
  is gated against),
* :mod:`container` — the versioned ``DCTZ`` container (magic, version,
  shape, quality, transform, table ids, CRC; version 3 adds colour
  components and two table classes) with :func:`encode_image` /
  :func:`decode_image`.

The encode path is a staged pipeline — symbolize -> table choice ->
codeword lookup -> prefix-sum offsets -> scatter-pack.  The encoders
take ``symbolizer``/``packer`` (and the decoders ``unpacker``) hooks
that :mod:`repro.kernels` fills with the Pallas route on a TPU; left
at ``None`` they are the host route.  The stage is exactly lossless
over the quantised levels, so ``decode_image(encode_image(img, q))``
reproduces the quantised round-trip reconstruction bit-exactly.  The
byte layout a third-party decoder needs is specified in
``docs/bitstream.md``.  This package (and the host halves
``encode_zigzag_host`` / ``decode_zigzag_host``) imports without jax.
"""

from repro.core.entropy.bitio import TruncatedStream
from repro.core.entropy.container import (BitstreamError, decode_image,
                                          decode_qcoeffs,
                                          decode_zigzag_host,
                                          encode_colour_zigzag_host,
                                          encode_image, encode_qcoeffs,
                                          encode_zigzag_host, read_header,
                                          stream_layout, verify_crc)

__all__ = ["BitstreamError", "TruncatedStream", "decode_image",
           "decode_qcoeffs", "decode_zigzag_host",
           "encode_colour_zigzag_host", "encode_image", "encode_qcoeffs",
           "encode_zigzag_host", "read_header", "stream_layout",
           "verify_crc"]
