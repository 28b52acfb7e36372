"""Device-resident RLE symbolisation (kernel / staged ref / routed ops).

The third kernel triplet of the entropy stack (after ``pack_bits`` and
``unpack_bits``): turns zig-zagged quantised blocks into the JPEG
(run, size) symbol stream, amplitude fields, per-block counts and the
per-alphabet histograms Huffman table choice needs — on device via the
Pallas kernel (TPU), or by the host symbolizer
(:mod:`repro.core.entropy.dense`) elsewhere.
"""

from repro.kernels.symbolize.ops import (BACKENDS, MAX_DEVICE_BLOCKS,
                                         TILE_BLOCKS, make_symbolizer,
                                         prepare, select_backend, symbolize,
                                         symbolize_dense)

__all__ = ["BACKENDS", "MAX_DEVICE_BLOCKS", "TILE_BLOCKS",
           "make_symbolizer", "prepare", "select_backend", "symbolize",
           "symbolize_dense"]
