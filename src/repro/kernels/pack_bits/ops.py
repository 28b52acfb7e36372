"""Routed public wrappers for the pack_bits kernel.

``pack_bits`` is the packing stage of the entropy encoders' ``packer``
hook: the Pallas kernel on TPU, the staged NumPy reference everywhere
else — the same backend-selection shape as ``fused_codec`` (compiled
kernel on TPU, bit-exact fallback elsewhere), and byte-identical output
either way (CI-gated by ``bench_entropy_throughput --check-identical``).
:func:`make_packer` is where the engine's encode picks the route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.kernels import tuning
from repro.kernels.pack_bits import kernel, ref

TILE_BITS = 1024                    # default output bits per kernel program
WINDOW_MARGIN = 128                 # window = tile_bits + this margin
WINDOW = TILE_BITS + WINDOW_MARGIN  # fields per window block (>= T+16)

# Above this many kept fields the stream packs with the NumPy
# reference.  VMEM does not bound it: the kernel streams two
# (3, window) int32 blocks per tile (a few tens of KiB at any M), and
# the field stack lives in HBM, 12 B per field after pow2 bucketing of
# the block count: compiled for a TPU v5e at this cap (294,912 padded
# fields at the default tile), ``memory_analysis()`` gives 4,734,976 B
# of arguments, 2,097,152 B of output and no temporaries.  The cap is
# the device route's tested range, which also caps the symbolize chain
# (``symbolize.MAX_DEVICE_BLOCKS``); raising it is ROADMAP A5.  2**18
# 16-bit fields is a ~512 KB payload.
MAX_DEVICE_FIELDS = 1 << 18

BACKENDS = ("pallas", "numpy")


def select_backend(backend: str = "auto") -> str:
    """Resolve the packing backend name ("pallas" on TPU, else "numpy")."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend not in BACKENDS:
        raise ValueError(f"unknown pack_bits backend {backend!r}; "
                         f"expected one of {('auto',) + BACKENDS}")
    return backend


def pack_bits(codes, lengths, *, backend: str = "auto",
              tile_bits: int | None = None,
              interpret: bool | None = None) -> bytes:
    """Concatenate MSB-first bit fields into padded payload bytes.

    Same contract as :func:`repro.core.entropy.bitio.pack_bits`
    (zero-width fields skipped, final partial byte 1-padded), with the
    packing stage routed per backend.

    Args:
        codes: (M,) non-negative ints; field k contributes its low
            ``lengths[k]`` bits, most significant first.
        lengths: (M,) field widths in [0, 16].
        backend: "auto" (Pallas on TPU, NumPy elsewhere), "pallas", or
            "numpy".
        tile_bits: output bits per kernel program (pow2, >= 64);
            ``None`` routes through the tuned-tile artifact
            (:func:`repro.kernels.tuning.tile_for`, falling back to
            :data:`TILE_BITS`).  Ignored by "numpy".  The field window
            is always ``tile_bits + WINDOW_MARGIN``.
        interpret: Pallas interpret-mode override (None = interpret
            exactly when no TPU is present); ignored by "numpy".

    Returns:
        The packed payload bytes, identical across backends and across
        every ``tile_bits``.
    """
    if select_backend(backend) == "numpy":
        with obs.route("pack", "host"):
            return ref.pack_bits_ref(codes, lengths)
    return _pack_bits_device(codes, lengths, interpret, tile_bits)


def make_packer():
    """The encode's packing route, chosen from the platform.

    ``None`` off the TPU — callers then keep their zero-indirection
    default (:func:`repro.core.entropy.bitio.pack_bits`) — and the
    routed device scatter-pack on a TPU.
    """
    if select_backend() == "numpy":
        return None
    return functools.partial(pack_bits, backend="pallas")


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@functools.partial(jax.jit, static_argnames=("n_tiles", "tile_bits",
                                             "window"))
def _window_blocks(ends, *, n_tiles: int, tile_bits: int, window: int):
    """Per tile, the window block holding its first overlapping field."""
    starts = jnp.arange(n_tiles, dtype=jnp.int32) * tile_bits
    first = jnp.searchsorted(ends, starts, side="right")
    last = ends.shape[0] // window - 2
    return jnp.minimum(first // window, last).astype(jnp.int32)


def field_blocks(m: int, tile_bits: int) -> int:
    """Padded field count for ``m`` kept fields: a pow2 number (>= 2)
    of ``tile_bits + WINDOW_MARGIN``-field blocks, so a streaming
    workload compiles a bounded set of shapes."""
    window = tile_bits + WINDOW_MARGIN
    return _pow2(-(-m // window) + 1) * window


def pack_fields_device(fields, total: int, tile_bits: int,
                       interpret: bool) -> bytes:
    """Device scatter-pack of a prepared ``(3, M)`` field stack.

    ``fields`` rows are codes, widths and starts (kept fields first in
    stream order, zero-width padding after them starting at ``total``),
    ``M = field_blocks(...)``.  Shared by :func:`pack_bits` and the
    symbolize chain, which builds the stack on device.
    """
    window = tile_bits + WINDOW_MARGIN
    n_tiles = _pow2(-(-total // tile_bits))
    blocks = _window_blocks(fields[1] + fields[2], n_tiles=n_tiles,
                            tile_bits=tile_bits, window=window)
    out = kernel.pack_bits_pallas(fields, blocks, tile_bits=tile_bits,
                                  window=window, interpret=interpret)
    obs.launched("pack", out)
    nbytes = (total + 7) // 8
    with obs.d2h(out):
        by = np.asarray(out).reshape(-1)[:nbytes].astype(np.uint8)
    pad = (-total) % 8
    if pad:                         # writer convention: 1-padded tail
        by[-1] |= (1 << pad) - 1
    return by.tobytes()


def _pack_bits_device(codes, lengths, interpret: bool | None,
                      tile_bits: int | None = None) -> bytes:
    """Host orchestration of the device scatter-pack.

    Stages 1–2 (filter + prefix-sum offsets) are O(M) NumPy; the
    per-tile window blocks and stage 3 run on the device.  Field and
    tile counts are bucketed to powers of two so a streaming workload
    sees a bounded set of compiled shapes.
    """
    from repro.kernels import common
    if interpret is None:
        interpret = common.interpret_default()
    c, ln, s, total = ref.field_layout(codes, lengths)
    if total == 0:
        return b""
    m = int(c.size)
    if m > MAX_DEVICE_FIELDS:
        with obs.route("pack", "host"):
            return ref.scatter_pack_ref(c, ln, s, total).tobytes()
    if tile_bits is None:
        tile_bits = tuning.tile_for("pack_bits", total)
    with obs.device_route("pack", interpret):
        fields = np.zeros((3, field_blocks(m, tile_bits)), np.int32)
        fields[0, :m], fields[1, :m], fields[2, :m] = c, ln, s
        fields[2, m:] = total
        with obs.h2d(fields):
            fields = jnp.asarray(fields)
        return pack_fields_device(fields, total, tile_bits, interpret)
