"""Batched multi-device codec pipeline.

The paper's throughput win comes from saturating the device with many
independent 8x8 blocks; this engine is the serving-side realisation:

* ``compress_batch`` / ``decompress_batch`` / ``roundtrip_batch`` accept a
  stacked ``(B, H, W)`` batch *or* a ragged list of mixed-size images,
* ragged images are edge-padded to **bucketed** shapes (next multiple of
  :data:`SHAPE_BUCKET`) so a service sees a bounded set of compiled shapes,
* the batch axis is padded to a power of two (same recompilation argument)
  and sharded over all local devices with shard_map on a 1-D "data" mesh
  (:func:`repro.launch.mesh.make_data_mesh`),
* on TPU the one-pass fused Pallas kernel (:mod:`repro.kernels.fused_codec`)
  handles roundtrips; everywhere else (and for compress/decompress halves)
  the batch-first :mod:`repro.core.codec` path runs, so CPU results are
  bit-identical to the single-image API,
* each group's output goes back as the program's own result (a stacked
  group at its own shape), one crop (a stacked group padded to the
  block; a decode bucket of one image size) or one slice per image (a
  ragged list; a decode bucket of mixed sizes), never restacked,
* ``encode_batch`` / ``decode_batch`` extend the same pipeline to real
  entropy-coded bytes: the array half stays sharded, the entropy stage
  (:mod:`repro.core.entropy`) runs per image at the host edge,
  *overlapped* with the device: jax async dispatch keeps bucket
  ``k+1``'s DCT/quant in flight while a thread pool (the vectorised
  NumPy entropy stage releases the GIL) codes bucket ``k``'s streams,
  and per-stream Huffman tables are memoised across repeated histogram
  shapes (``huffman.build_table_memo``).  Each entropy stage takes one
  route per platform, chosen by its kernel package's ``make_*``
  factory (:func:`repro.kernels.symbolize.make_symbolizer`,
  :func:`repro.kernels.pack_bits.make_packer`,
  :func:`repro.kernels.unpack_bits.make_unpacker`): the Pallas kernels
  within their size guards on TPU, the host NumPy routes elsewhere, with
  identical bytes.  The table policy (``tables``) can pin embedded or
  well-known shared Huffman tables per stream.

The fused kernel reconstructs with the *matched* (adjoint) transform, so it
only serves roundtrips whose semantics agree with it: ``transform="exact"``
(both decode modes coincide) or ``mode="matched"``.  A standards-compliant
decode of a CORDIC stream always takes the staged path.

Colour images — a stacked ``(B, H, W, 3)`` uint8 batch or ``(H, W, 3)``
entries of a ragged list — take the same path as baseline YCbCr 4:2:0
(:mod:`repro.core.colour`): their own sharded programs
(``_compress_sharded_colour``, ``_decompress_sharded_colour``) convert,
subsample, transform and quantise three planes and interleave the
levels into MCU order with zig-zag on the device, and the entropy stage
writes ``DCTZ`` version-3 streams with two Huffman table classes.  A
ragged list may mix grayscale and colour images: they bucket apart.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.core import codec, colour, cordic, metrics
from repro.launch import mesh as mesh_lib

SHAPE_BUCKET = 64      # ragged H/W round up to this (multiple of the block)


def _n_workers() -> int:
    """Thread-pool width for the host-edge entropy stage."""
    return max(1, min(8, os.cpu_count() or 1))


# ---------------------------------------------------------------------------
# Batch containers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompressedGroup:
    """Images sharing one padded bucket shape, compressed together.

    A colour group holds, per image, its (bh/16, bw/16, 6, 64) levels:
    MCUs of ``Y00 Y01 Y10 Y11 Cb Cr`` blocks, each in zig-zag order."""
    qcoeffs: jnp.ndarray           # (n, bh/8, bw/8, 8, 8) int32
    indices: tuple                 # positions in the original input order
    orig_shapes: tuple             # per-image (H, W) before padding
    colour: bool = False

    def grid(self, h: int, w: int) -> tuple:
        """An image's own block (or, for colour, MCU) grid."""
        return colour.mcu_grid(h, w) if self.colour else \
            ((h + 7) // 8, (w + 7) // 8)


@dataclasses.dataclass
class CompressedBatch:
    """Quantised DCT representation of a batch of grayscale images."""
    groups: list
    n_images: int
    quality: int
    transform: str
    cordic_config: cordic.CordicConfig
    stacked: bool                  # input was a single (B, H, W) array
    # (tables_policy, streams) — byte output depends on the table
    # policy but never on the entropy route (enforced by the
    # --check-identical gate), so the cache keys on the former only
    _streams: tuple | None = dataclasses.field(
        default=None, repr=False, compare=False)

    def nbytes_estimate(self) -> float:
        """Total compressed size of the batch, in bytes.

        Two regimes, by how much work has been done:

        * **measured** — once :meth:`to_bytes_list` has materialised the
          entropy-coded streams, this returns their exact summed
          ``len()`` (the number every ratio in RESULTS.md is built on);
        * **estimated** — before that, it falls back to the device-side
          :func:`repro.core.quant.estimate_bits` proxy over the
          (bucket-padded) levels — the repo's one surviving size
          estimator, kept exactly for this pre-materialisation
          telemetry — which needs no host transfer or bit packing but
          overstates ragged batches (padding blocks count) and is only
          a model of the entropy coder.

        Callers that need the measured number unconditionally should
        call ``sum(len(s) for s in batch.to_bytes_list())`` and pay for
        the coding.
        """
        if self._streams is not None:
            return float(sum(len(s) for s in self._streams[1]))
        from repro.core import quant
        return sum(float(quant.estimate_bits(g.qcoeffs)) / 8.0
                   for g in self.groups)

    def to_bytes_list(self, tables: str = "auto") -> list:
        """Entropy-code every image: list of ``DCTZ`` streams in input
        order (measured per-image byte sizes via ``len()``).

        The host edge is overlapped with the device: groups are drained
        in dispatch order, and as soon as one group's levels land on
        the host its images are handed to a thread pool (NumPy releases
        the GIL inside the vectorised symbolisation/packing), while
        jax's async dispatch keeps the *next* group's DCT/quant running
        on the device.  Symbolize and pack take the platform's route
        (:func:`repro.kernels.symbolize.make_symbolizer`,
        :func:`repro.kernels.pack_bits.make_packer`): on TPU the
        workers enqueue the device symbolize → scatter-pack chain per
        stream within its size guards, so only histograms and payload
        bytes cross to the host; elsewhere, and above the guards, the
        host symbolizer and NumPy packer run in the worker.  Results
        are cached on the batch per table policy, so repeated calls
        (and :meth:`nbytes_estimate` afterwards) are free.

        Args:
            tables: Huffman table policy per stream ("auto" /
                "embedded" / "shared"), see
                :func:`repro.core.entropy.encode_qcoeffs`.
        """
        from repro.core import entropy
        from repro.core.entropy import scan
        from repro.kernels import pack_bits, symbolize
        if self._streams is not None and self._streams[0] == tables:
            return list(self._streams[1])
        packer = pack_bits.make_packer()
        symbolizer = symbolize.make_symbolizer()
        call = obs.current_call()
        # dispatch the zig-zag for every bucket up front: jax queues the
        # device work asynchronously, so bucket k+1 computes while the
        # pool below is still coding bucket k's streams (a colour
        # group's program already wrote its levels in zig-zag order)
        zs = [g.qcoeffs if g.colour else scan.zigzag_scan(g.qcoeffs)
              for g in self.groups]
        jobs: list = [None] * self.n_images
        with concurrent.futures.ThreadPoolExecutor(_n_workers()) as pool:
            for g, z in zip(self.groups, zs):
                # blocks only on THIS bucket's device work
                with obs.d2h(z):
                    znp = np.asarray(jax.device_get(z))
                encode = (entropy.encode_colour_zigzag_host if g.colour
                          else entropy.encode_zigzag_host)
                for j, (idx, (h, w)) in enumerate(zip(g.indices,
                                                      g.orig_shapes)):
                    gh, gw = g.grid(h, w)
                    jobs[idx] = pool.submit(
                        _encode_image, call, idx, encode,
                        znp[j, :gh, :gw].reshape(-1, 64), gh * gw,
                        g.colour, self.quality, self.transform, (h, w),
                        tables=tables, packer=packer,
                        symbolizer=symbolizer)
            self._streams = (tables, [f.result() for f in jobs])
        self._count_encoded()
        return list(self._streams[1])

    def _count_encoded(self) -> None:
        obs.count("engine.images.encoded", self.n_images)
        n_colour = sum(len(g.indices) for g in self.groups if g.colour)
        if n_colour:
            obs.count("engine.images.colour.encoded", n_colour)


def _encode_image(call: int, image: int, encode, levels, units: int,
                  rgb: bool, *args, **kwargs) -> bytes:
    """``encode(levels, *args, **kwargs)`` in the image's span; ``units``
    is its block count, or for a colour image its MCU count."""
    blocks = units * colour.BLOCKS_PER_MCU if rgb else units
    with obs.span("entropy.encode_image", call=call, image=image,
                  blocks=blocks, components=3 if rgb else 1, mcus=units):
        return encode(levels, *args, **kwargs)


def _decode_image(decode, call: int, image: int, blob):
    """``decode(blob)`` in the image's span."""
    from repro.core import entropy
    components, mcus = entropy.stream_layout(blob)
    with obs.span("entropy.decode_image", call=call, image=image,
                  stream_bytes=len(blob), components=components, mcus=mcus):
        return decode(blob)


# ---------------------------------------------------------------------------
# Device sharding
# ---------------------------------------------------------------------------

def _n_devices() -> int:
    return jax.local_device_count()


def _shard_data(body, n_dev: int):
    """``body`` shard_mapped over the 1-D "data" mesh of ``n_dev`` devices."""
    return jax.shard_map(body, mesh=mesh_lib.make_data_mesh(n_dev),
                         in_specs=P("data"), out_specs=P("data"),
                         check_vma=False)


def _pad_rows(n: int, n_dev: int) -> int:
    """Bucketed batch size: next power of two, then up to a device multiple."""
    b = 1
    while b < n:
        b *= 2
    return b + (-b) % n_dev


def _bucket_dim(d: int) -> int:
    return d + (-d) % SHAPE_BUCKET


@functools.partial(jax.jit, static_argnames=("transform", "quality",
                                             "cordic_config", "n_dev"))
def _compress_sharded_colour(imgs, transform, quality, cordic_config, n_dev):
    body = lambda x: colour.compress_batch_mcus(x, transform, quality,
                                                cordic_config)
    if n_dev == 1:
        return body(imgs)
    return _shard_data(body, n_dev)(imgs)


@functools.partial(jax.jit, static_argnames=("transform", "quality",
                                             "cordic_config", "n_dev"))
def _decompress_sharded_colour(z, transform, quality, cordic_config,
                               n_dev):
    body = lambda q: colour.decompress_batch_mcus(q, transform, quality,
                                                  cordic_config)
    if n_dev == 1:
        return body(z)
    return _shard_data(body, n_dev)(z)


@functools.partial(jax.jit, static_argnames=("transform", "quality",
                                             "cordic_config", "n_dev"))
def _compress_sharded(imgs, transform, quality, cordic_config, n_dev):
    body = lambda x: codec.compress_batch_blocks(x, transform, quality,
                                                 cordic_config)
    if n_dev == 1:
        return body(imgs)
    return _shard_data(body, n_dev)(imgs)


@functools.partial(jax.jit, static_argnames=("transform", "quality",
                                             "cordic_config", "n_dev"))
def _decompress_sharded(qcoeffs, transform, quality, cordic_config, n_dev):
    body = lambda q: codec.decompress_batch_blocks(q, transform, quality,
                                                   cordic_config)
    if n_dev == 1:
        return body(qcoeffs)
    return _shard_data(body, n_dev)(qcoeffs)


@functools.partial(jax.jit, static_argnames=("transform", "quality",
                                             "cordic_config", "n_dev"))
def _fused_roundtrip_sharded(imgs, transform, quality, cordic_config, n_dev):
    from repro.kernels.fused_codec import fused_codec

    def body(x):
        rec, _ = fused_codec(x, quality=quality, transform=transform,
                             config=cordic_config)
        return rec
    if n_dev == 1:
        return body(imgs)
    return _shard_data(body, n_dev)(imgs)


def _run_batched(fn, arr: jnp.ndarray) -> jnp.ndarray:
    """Pad the leading axis to the batch bucket, run sharded, and crop
    the padding rows back off; unpadded, the program's output is
    returned as it is."""
    n = arr.shape[0]
    n_dev = _n_devices()
    padded_n = _pad_rows(n, n_dev)
    if padded_n == n:
        return fn(arr, n_dev)
    arr = jnp.concatenate(
        [arr, jnp.zeros((padded_n - n, *arr.shape[1:]), arr.dtype)])
    return fn(arr, n_dev)[:n]


@functools.partial(jax.jit, static_argnames=("h", "w"))
def _crop_each(rec: jnp.ndarray, h: int, w: int) -> tuple:
    """Every image of ``rec`` cropped to (h, w), one array each, from one
    program."""
    return tuple(rec[j, :h, :w] for j in range(rec.shape[0]))


# ---------------------------------------------------------------------------
# Input normalisation (stacked vs ragged)
# ---------------------------------------------------------------------------

def _to_device(x):
    """``x`` as a device array: a host array's copy is an ``xfer.h2d``
    span, an array already on the device is returned as it is."""
    if isinstance(x, jax.Array):
        return x
    x = np.asarray(x)
    with obs.h2d(x):
        return jnp.asarray(x)


def _n_images(imgs) -> int:
    return len(imgs) if getattr(imgs, "ndim", 1) else 0


def _group_inputs(imgs):
    """Yield (stacked_padded_uint8, indices, orig_shapes, colour) bucket
    groups.

    A stacked (B, H, W) array is one group padded to the 8-block like the
    single-image API, a stacked (B, H, W, 3) colour batch one group
    padded to 16x16 MCUs.  A ragged list buckets each image's H/W up to
    SHAPE_BUCKET and groups equal buckets (grayscale and colour apart)
    so B mixed sizes cost at most O(#distinct buckets) compilations, not
    O(B).
    """
    if isinstance(imgs, (np.ndarray, jnp.ndarray)):
        arr = _to_device(imgs)
        rgb = arr.ndim == 4 and arr.shape[-1] == 3
        if arr.ndim != 3 and not rgb:
            raise ValueError(f"stacked batch must be (B, H, W) or "
                             f"(B, H, W, 3), got {arr.shape}")
        if arr.shape[0] == 0:
            raise ValueError("empty batch: nothing to compress")
        h, w = arr.shape[1:3]
        padded = colour.pad_to_mcu(arr) if rgb else codec.pad_to_block(arr)
        return [(padded, tuple(range(arr.shape[0])),
                 tuple((h, w) for _ in range(arr.shape[0])), rgb)], True

    if not len(imgs):
        raise ValueError("empty batch: nothing to compress")
    buckets: dict = {}
    for i, im in enumerate(imgs):
        im = _to_device(im)
        rgb = colour.is_colour(im) and im.ndim == 3
        if im.ndim != 2 and not rgb:
            raise ValueError(f"image {i} must be (H, W) or (H, W, 3), "
                             f"got {im.shape}")
        h, w = im.shape[:2]
        key = (_bucket_dim(h), _bucket_dim(w), rgb)
        buckets.setdefault(key, []).append((i, im))

    groups = []
    for (bh, bw, rgb), members in buckets.items():
        chan = [(0, 0)] if rgb else []
        padded = jnp.stack([
            jnp.pad(im, [(0, bh - im.shape[0]), (0, bw - im.shape[1])]
                    + chan, mode="edge") for _, im in members])
        groups.append((padded,
                       tuple(i for i, _ in members),
                       tuple(tuple(im.shape[:2]) for _, im in members),
                       rgb))
    return groups, False


def _reassemble(per_group: list, groups: list, n: int, stacked: bool):
    """Per-group outputs back in input order at their original sizes.

    A stacked input is one group in input order: its output is the
    answer when padding to the block added nothing, else one crop of
    the whole group.  A ragged list comes back as a list, one slice per
    image.  Each group counts its route as
    ``engine.reassemble.<direct|program|per_image>``.
    """
    with obs.span("engine.reassemble"):
        if stacked:
            (_, _, orig_shapes, *_), imgs_out = groups[0], per_group[0]
            h, w = orig_shapes[0]
            if imgs_out.shape[1:3] == (h, w):
                obs.count("engine.reassemble.direct")
                return imgs_out
            obs.count("engine.reassemble.program")
            return imgs_out[:, :h, :w]
        out = [None] * n
        for imgs_out, (_, indices, orig_shapes, *_) in zip(per_group,
                                                          groups):
            obs.count("engine.reassemble.per_image")
            for j, (idx, (h, w)) in enumerate(zip(indices, orig_shapes)):
                out[idx] = imgs_out[j, :h, :w]
        return out


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

def compress_batch(imgs, quality: int = 50,
                   transform: codec.Transform = "exact",
                   cordic_config: cordic.CordicConfig = cordic.PAPER_CONFIG
                   ) -> CompressedBatch:
    """Compress a (B, H, W) batch or ragged list of grayscale images,
    or colour (H, W, 3) RGB ones.

    Args:
        imgs: either a stacked (B, H, W) uint8/float array (one compiled
            shape), a stacked (B, H, W, 3) colour batch, or a list of
            (H, W) or (H, W, 3) images of mixed sizes; ragged sizes
            bucket up to multiples of :data:`SHAPE_BUCKET` and equal
            buckets are compressed together.
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform, see :data:`repro.core.codec.Transform`.
        cordic_config: CORDIC config for ``transform == "cordic"``.

    Returns:
        A :class:`CompressedBatch` whose groups hold (n, bh/8, bw/8, 8, 8)
        int32 quantised levels per bucket shape (colour groups: (n,
        bh/16, bw/16, 6, 64) interleaved zig-zag levels), plus the
        bookkeeping to restore input order and crop back to original
        sizes.
    """
    with obs.span("engine.compress"):
        groups, stacked = _group_inputs(imgs)
        out = []
        n = 0
        for padded, indices, orig_shapes, rgb in groups:
            fn = functools.partial(
                _compress_sharded_colour if rgb else _compress_sharded,
                transform=transform, quality=quality,
                cordic_config=cordic_config)
            q = _run_batched(
                lambda a, nd: fn(a, n_dev=nd), padded)
            out.append(CompressedGroup(qcoeffs=q, indices=indices,
                                       orig_shapes=orig_shapes, colour=rgb))
            n += len(indices)
    return CompressedBatch(groups=out, n_images=n, quality=quality,
                           transform=transform, cordic_config=cordic_config,
                           stacked=stacked)


def decompress_batch(cb: CompressedBatch, mode: str = "standard"):
    """Reconstruct every image.  Returns (B, H, W) uint8 when the input was
    stacked, else a list of per-image uint8 arrays in input order.

    ``mode`` follows :func:`repro.core.codec.decompress`: "standard" decodes
    with the exact IDCT, "matched" with the encoder's adjoint.

    Args:
        cb: a :class:`CompressedBatch` from :func:`compress_batch`.
        mode: "standard" (exact IDCT, standards-compliant) or "matched"
            (encoder's adjoint; CORDIC angle error largely cancels).

    Returns:
        (B, H, W) uint8 array when the input was stacked, else a list of
        (H, W) uint8 arrays, each cropped to its original size; colour
        images come back (H, W, 3).
    """
    dec_transform = "exact" if mode == "standard" else cb.transform
    with obs.span("engine.inverse"):
        per_group = []
        for g in cb.groups:
            fn = functools.partial(
                _decompress_sharded_colour if g.colour
                else _decompress_sharded, transform=dec_transform,
                quality=cb.quality, cordic_config=cb.cordic_config)
            per_group.append(_run_batched(lambda a, nd: fn(a, n_dev=nd),
                                          g.qcoeffs))
    groups = [(None, g.indices, g.orig_shapes) for g in cb.groups]
    return _reassemble(per_group, groups, cb.n_images, cb.stacked)


@functools.partial(jax.jit)
def _psnr_vec(orig: jnp.ndarray, rec: jnp.ndarray) -> jnp.ndarray:
    return jax.vmap(metrics.psnr)(orig, rec)


def _fused_ok(transform: str, mode: str) -> bool:
    return jax.default_backend() == "tpu" and (
        transform == "exact" or mode == "matched")


def roundtrip_batch(imgs, quality: int = 50,
                    transform: codec.Transform = "exact",
                    cordic_config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                    mode: str = "standard", with_psnr: bool = True):
    """Batched form of :func:`repro.core.codec.roundtrip`.

    On TPU the one-pass fused Pallas kernel serves compatible
    (transform, mode) combinations — ``transform == "exact"`` or
    ``mode == "matched"`` (the kernel reconstructs with the matched
    adjoint); the staged compress+decompress path is the CPU fallback
    and the bit-exact reference (docs/architecture.md).

    Args:
        imgs: stacked (B, H, W) array or ragged list of (H, W) images,
            as in :func:`compress_batch`.
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform ("exact"/"cordic"/"loeffler").
        cordic_config: CORDIC config for ``transform == "cordic"``.
        mode: decode mode, see :func:`decompress_batch`.
        with_psnr: also score each reconstruction against its input.

    Returns:
        ``(reconstructed, psnr)``: ``reconstructed`` is (B, H, W) uint8
        for stacked input (a list for ragged input); ``psnr`` is a (B,)
        numpy array of dB values, or None when ``with_psnr=False``.
    """
    with obs.call("engine.roundtrip", images=_n_images(imgs)):
        return _roundtrip(imgs, quality, transform, cordic_config, mode,
                          with_psnr)


def _has_colour(imgs) -> bool:
    if isinstance(imgs, (np.ndarray, jnp.ndarray)):
        return imgs.ndim == 4
    return any(np.ndim(im) == 3 for im in imgs)


def _roundtrip(imgs, quality, transform, cordic_config, mode, with_psnr):
    # the fused kernel codes grayscale planes only
    if _fused_ok(transform, mode) and not _has_colour(imgs):
        obs.count("engine.roundtrip.fused")
        groups, stacked = _group_inputs(imgs)
        fn = functools.partial(_fused_roundtrip_sharded, transform=transform,
                               quality=quality, cordic_config=cordic_config)
        per_group = [_run_batched(lambda a, nd: fn(a, n_dev=nd), padded)
                     for padded, *_ in groups]
        n = sum(len(g[1]) for g in groups)
        rec = _reassemble(per_group, groups, n, stacked)
    else:
        obs.count("engine.roundtrip.staged")
        cb = compress_batch(imgs, quality, transform, cordic_config)
        rec = decompress_batch(cb, mode=mode)

    if not with_psnr:
        return rec, None
    with obs.span("engine.psnr"):
        if isinstance(rec, list):
            vals = [metrics.psnr(_to_device(im), r)
                    for im, r in zip(imgs, rec)]
            with obs.d2h(*vals):
                psnr = np.array([float(v) for v in jax.device_get(vals)])
        else:
            vals = _psnr_vec(_to_device(imgs), rec)
            with obs.d2h(vals):
                psnr = np.asarray(jax.device_get(vals))
    return rec, psnr


# ---------------------------------------------------------------------------
# Entropy-coded byte path (real bytes per image)
# ---------------------------------------------------------------------------

def encode_batch(imgs, quality: int = 50,
                 transform: codec.Transform = "exact",
                 cordic_config: cordic.CordicConfig = cordic.PAPER_CONFIG,
                 tables: str = "auto") -> list:
    """Compress a batch all the way to entropy-coded ``DCTZ`` streams.

    The array half (DCT + quantise) runs the sharded
    :func:`compress_batch` path unchanged; the per-image entropy stage
    happens at the host edge, overlapped with the device: jax's async
    dispatch queues *every* bucket's device work up front, and a thread
    pool entropy-codes bucket *k* while the device is still crunching
    bucket *k+1* (:meth:`CompressedBatch.to_bytes_list`, which also
    says where each entropy stage runs).

    Args:
        imgs: stacked (B, H, W) array or ragged list of (H, W) images,
            as in :func:`compress_batch`; colour images, stacked (B, H,
            W, 3) or (H, W, 3) entries of a list, are coded as ``DCTZ``
            version-3 YCbCr 4:2:0 streams.
        quality: JPEG quality factor in [1, 100].
        transform: encoder transform ("exact"/"cordic"/"loeffler").
        cordic_config: CORDIC config for ``transform == "cordic"``.
        tables: Huffman table policy ("auto"/"embedded"/"shared").

    Returns:
        List of ``bytes`` (one ``DCTZ`` stream per image, input order);
        each is bit-identical to ``core.codec.compress(img).to_bytes()``
        under the same table policy.
    """
    with obs.call("engine.encode", images=_n_images(imgs)):
        cb = compress_batch(imgs, quality, transform, cordic_config)
        return cb.to_bytes_list(tables=tables)


def decode_batch(blobs, mode: str = "standard") -> list:
    """Decode a list of ``DCTZ`` streams through the sharded array path.

    Streams are entropy-decoded on the host edge — concurrently on a
    thread pool when there is more than one — then grouped by
    block-grid shape + quality + decode transform, and each group runs
    one sharded ``decompress`` jit; the byte path re-joins the array
    path right after the bitstream boundary.

    The entropy decode takes the platform's route
    (:func:`repro.kernels.unpack_bits.make_unpacker`): on TPU the
    Pallas decode, which resolves each stream's block chain on the
    device within its guards, and the pool overlaps each stream's
    device unpack with the host-side parse/CRC of its neighbours;
    elsewhere the LUT walk.

    Args:
        blobs: iterable of ``DCTZ`` streams (``bytes``).
        mode: "standard" (exact IDCT) or "matched" (stored transform's
            adjoint), as in :func:`decompress_batch`.

    Returns:
        List of (H, W) uint8 reconstructions in input order, each
        bit-identical to the single-image
        ``codec.decompress(CompressedImage.from_bytes(blob), mode)``;
        (H, W, 3) uint8 RGB for a colour stream, bit-identical to
        ``entropy.decode_image(blob, mode)``.

    Raises:
        repro.core.entropy.BitstreamError: any malformed stream (the
        whole call fails; no partial results).
        ValueError: an empty batch.
    """
    from repro.core import entropy
    from repro.kernels import unpack_bits
    unpacker = unpack_bits.make_unpacker()
    decode_one = functools.partial(entropy.decode_zigzag_host,
                                   unpacker=unpacker)
    blobs = list(blobs)
    if not blobs:
        raise ValueError("empty batch: nothing to decode")
    with obs.call("engine.decode", images=len(blobs)) as call:
        out = _decode(blobs, decode_one, call, mode)
    obs.count("engine.images.decoded", len(blobs))
    return out


def _decode(blobs, decode_one, call, mode):
    from repro.core.entropy import scan
    traced = functools.partial(_decode_image, decode_one, call)
    if len(blobs) > 1:
        # each stream's entropy decode is independent host/device work
        with concurrent.futures.ThreadPoolExecutor(_n_workers()) as pool:
            decoded = list(pool.map(traced, range(len(blobs)), blobs))
    else:
        decoded = [traced(0, blobs[0])]

    with obs.span("engine.inverse"):
        buckets: dict = {}
        for i, (z, hdr) in enumerate(decoded):
            dec_transform = ("exact" if mode == "standard"
                             else hdr["transform"])
            rgb = hdr.get("components", 1) == 3
            grid = (colour.mcu_grid(hdr["height"], hdr["width"]) if rgb
                    else ((hdr["height"] + 7) // 8, (hdr["width"] + 7) // 8))
            key = (grid, hdr["quality"], dec_transform, rgb)
            buckets.setdefault(key, []).append(i)

        out = [None] * len(blobs)
        n_colour = 0
        for ((gh, gw), quality, dec_transform, rgb), members in \
                buckets.items():
            zs = [decoded[i][0] for i in members]
            with obs.h2d(*zs):
                stackz = jnp.stack([jnp.asarray(z) for z in zs])
            if rgb:
                # the colour program un-zig-zags and de-interleaves itself
                stackq = stackz.reshape(-1, gh, gw, colour.BLOCKS_PER_MCU,
                                        64)
                n_colour += len(members)
            else:
                # device half of the inverse: un-zig-zag the whole group
                stackq = scan.zigzag_unscan(stackz).reshape(-1, gh, gw, 8,
                                                            8)
            fn = functools.partial(
                _decompress_sharded_colour if rgb else _decompress_sharded,
                transform=dec_transform, quality=quality,
                cordic_config=cordic.PAPER_CONFIG)
            rec = _run_batched(lambda a, nd: fn(a, n_dev=nd), stackq)
            sizes = [(decoded[i][1]["height"], decoded[i][1]["width"])
                     for i in members]
            with obs.span("engine.reassemble"):
                if len(set(sizes)) == 1:
                    obs.count("engine.reassemble.program")
                    crops = _crop_each(rec, *sizes[0])
                else:
                    obs.count("engine.reassemble.per_image")
                    crops = [rec[j, :h, :w] for j, (h, w) in
                             enumerate(sizes)]
            for i, im in zip(members, crops):
                out[i] = im
    if n_colour:
        obs.count("engine.images.colour.decoded", n_colour)
    return out
