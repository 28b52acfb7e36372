"""Routed public wrappers for the symbolize kernel.

``symbolize`` / ``symbolize_dense`` route one batch of blocks per
backend — the Pallas kernel on TPU, the host symbolizer
(:mod:`repro.core.entropy.dense`) everywhere else — element-identical
either way (CI-gated by ``bench_entropy_throughput --check-identical``).

:func:`prepare` is the two-phase *prepared stream* the container
encoders thread through (``symbolizer=``): the alphabet histograms
first (all the host needs for Huffman table negotiation), the payload
bytes once tables are chosen.  On the Pallas route that second phase
chains entirely on device — dense codeword gather, stable zero-width
compaction, prefix-sum offsets, then the ``pack_bits`` scatter-pack
kernel — so the host transfers two 1 KiB histograms, one scalar bit
count and the finished payload instead of the full coefficient tensor.
Streams the device guards reject take the host symbolizer.
:func:`make_symbolizer` is where the engine's encode picks the route.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.entropy import dense, huffman, rle
from repro.kernels import tuning
from repro.kernels.pack_bits import ops as pack_ops
from repro.kernels.symbolize import kernel

TILE_BLOCKS = 64                    # default blocks per kernel program

# Above this many blocks the stream takes the host symbolizer: the
# chained payload stage packs the (2 * 64 * n_pad,) dense field slots
# through pack_bits, so its MAX_DEVICE_FIELDS cap divided by the 128
# fields a block can emit caps the device-resident block count (2048
# blocks: 256x256 images stay on device, 512x512 do not — ROADMAP A3).
MAX_DEVICE_BLOCKS = pack_ops.MAX_DEVICE_FIELDS // (2 * dense.SLOTS)

# The kernel computes magnitude categories as 15 threshold compares in
# int32, so levels must already fit 15-bit amplitudes; anything larger
# is routed to the reference, which raises the oracle's RangeError.
_MAX_DEVICE_LEVEL = 1 << rle.MAX_CATEGORY

BACKENDS = ("pallas", "numpy")


def select_backend(backend: str = "auto") -> str:
    """Resolve the symbolize backend ("pallas" on TPU, else "numpy")."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "numpy"
    if backend not in BACKENDS:
        raise ValueError(f"unknown symbolize backend {backend!r}; "
                         f"expected one of {('auto',) + BACKENDS}")
    return backend


def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _device_ok(dc_diff: np.ndarray, ac: np.ndarray) -> bool:
    """True when the kernel's int32/15-bit preconditions hold."""
    n = dc_diff.shape[0]
    if n == 0 or n > MAX_DEVICE_BLOCKS:
        return False
    if n and int(np.abs(dc_diff).max()) >= _MAX_DEVICE_LEVEL:
        return False
    if ac.size and int(np.abs(ac).max()) >= _MAX_DEVICE_LEVEL:
        return False
    return True


def _run_kernel(dc_diff: np.ndarray, ac: np.ndarray, tile_blocks: int,
                interpret: bool, classes: tuple = rle.ONE_CLASS) -> tuple:
    """Pad, launch, and return the kernel's device outputs + n_pad."""
    n = dc_diff.shape[0]
    n_pad = max(_pow2(n), tile_blocks)
    dc = np.zeros((n_pad, 1), np.int32)
    dc[:n, 0] = dc_diff
    acp = np.zeros((n_pad, dense.AC_LEN), np.int32)
    acp[:n] = ac
    nrows = np.array([n], np.int32)
    host = [dc, acp, nrows]
    if classes != rle.ONE_CLASS:
        host.append(rle.block_classes(classes, n_pad).astype(
            np.int32).reshape(n_pad, 1))
    with obs.h2d(*host):
        args = [jnp.asarray(a) for a in host]
    out = kernel.symbolize_pallas(*args, tile_blocks=tile_blocks,
                                  n_classes=max(classes) + 1,
                                  interpret=interpret)
    obs.launched("symbolize", out[0])
    return out


def symbolize_dense(dc_diff, ac, *, backend: str = "auto",
                    tile_blocks: int | None = None,
                    interpret: bool | None = None,
                    classes: tuple = rle.ONE_CLASS) -> dense.DenseSymbols:
    """Routed fused pass: dense slots + histograms on the host.

    Args:
        dc_diff: (n,) int DC differences in block order.
        ac: (n, 63) int AC tails in zig-zag order.
        backend: "auto" (Pallas on TPU, NumPy elsewhere), "pallas", or
            "numpy".
        tile_blocks: blocks per kernel program (pow2); ``None`` routes
            through the tuned-tile artifact
            (:func:`repro.kernels.tuning.tile_for`).  Ignored by
            "numpy".
        interpret: Pallas interpret-mode override; ignored by "numpy".
        classes: the blocks' table-class pattern; with more than one
            class the histograms are (n_classes, 256).

    Returns:
        A :class:`repro.core.entropy.dense.DenseSymbols`, identical
        across backends and every ``tile_blocks``.

    Raises:
        rle.RangeError: some level needs an amplitude wider than 15
            bits (the oracle's exact message, whichever backend runs).
    """
    dc_diff = np.asarray(dc_diff, dtype=np.int64)
    ac = np.asarray(ac, dtype=np.int64)
    n = dc_diff.shape[0]
    if select_backend(backend) == "numpy" or not _device_ok(dc_diff, ac):
        with obs.route("symbolize", "host", blocks=n):
            return dense.symbolize_dense(dc_diff, ac, classes)
    from repro.kernels import common
    if interpret is None:
        interpret = common.interpret_default()
    if tile_blocks is None:
        tile_blocks = tuning.tile_for("symbolize", n)
    with obs.device_route("symbolize", interpret, blocks=n):
        out = _run_kernel(dc_diff, ac, tile_blocks, interpret, classes)
        with obs.d2h(*out):
            syms, amps, lens, total, dc_h, ac_h = jax.device_get(out)
    return dense.DenseSymbols(
        syms=np.asarray(syms[:n], np.int16),
        amp_vals=np.asarray(amps[:n], np.int16),
        amp_lens=np.asarray(lens[:n], np.int16),
        total=np.asarray(total[:n, 0], np.int64),
        dc_freq=_hist(dc_h, classes), ac_freq=_hist(ac_h, classes),
        classes=classes)


def _hist(h, classes: tuple) -> np.ndarray:
    """A fetched (n_classes, 256) histogram as the host reference shapes
    it: one (256,) row for a one-class stream."""
    h = np.asarray(h, np.int64)
    return h[0] if classes == rle.ONE_CLASS else h


def symbolize(dc_diff, ac, *, backend: str = "auto",
              tile_blocks: int | None = None,
              interpret: bool | None = None) -> tuple:
    """Routed symbol stream: the contract and return dtypes of
    :func:`repro.core.entropy.rle.symbolize_reference` (``(is_dc, syms,
    amp_vals, amp_lens)``), element-identical to that oracle across
    every backend and tile (CI-gated).
    """
    return dense.dense_to_stream(symbolize_dense(
        dc_diff, ac, backend=backend, tile_blocks=tile_blocks,
        interpret=interpret))


# ---------------------------------------------------------------------------
# Prepared streams: the container's symbolizer= protocol
# ---------------------------------------------------------------------------

@jax.jit
def _fields_device(syms, amps, lens, total, dc_code, dc_len,
                   ac_code, ac_len, cls=None):
    """Dense codeword gather + stable zero-width compaction, on device.

    Returns the flattened field/width/start arrays ready for the
    scatter-pack kernel (kept fields first, in stream order; zero-width
    tail at offset ``total_bits``), plus the payload bit count and an
    uncodeable-symbol flag.  With ``cls`` ((n_pad, 1) table class per
    block) the code tables are (n_classes, 256) and each block takes
    its class's row.
    """
    slot = jnp.arange(dense.SLOTS, dtype=jnp.int32)[None, :]
    valid = slot < total                                    # (n_pad, 64)
    isdc = slot == 0
    if cls is None:
        codes = jnp.where(isdc, dc_code[syms], ac_code[syms])
        clens = jnp.where(isdc, dc_len[syms], ac_len[syms])
    else:
        codes = jnp.where(isdc, dc_code[cls, syms], ac_code[cls, syms])
        clens = jnp.where(isdc, dc_len[cls, syms], ac_len[cls, syms])
    bad = jnp.any((clens == 0) & valid)
    f = jnp.stack([codes, amps], axis=-1).reshape(-1)
    w = jnp.stack([jnp.where(valid, clens, 0),
                   jnp.where(valid, lens, 0)], axis=-1).reshape(-1)
    f = f & ((1 << w) - 1)          # only the low `w` bits are payload
    # stable partition without sorting: kept fields keep stream order,
    # zero-width fields move to the tail
    kept = w > 0
    m = f.shape[0]
    n_kept = jnp.cumsum(kept.astype(jnp.int32))
    dest = jnp.where(kept, n_kept - 1,
                     n_kept[-1] + jnp.cumsum((~kept).astype(jnp.int32)) - 1)
    f2 = jnp.zeros((m,), f.dtype).at[dest].set(f)
    w2 = jnp.zeros((m,), w.dtype).at[dest].set(w)
    ends = jnp.cumsum(w2)
    return f2, w2, ends - w2, ends[-1], bad


class _PallasPrepared:
    """Device-resident preparation: histograms now, device pack later.

    Construction runs the symbolize kernel and pulls only the two
    (1, 256) histograms; :meth:`payload` chains codeword gather →
    prefix-sum offsets → scatter-pack on device and pulls the finished
    bytes (plus one scalar bit count to size the tile grid).
    """

    def __init__(self, dc_diff, ac, tile_blocks, interpret,
                 classes=rle.ONE_CLASS):
        self._interpret = interpret
        self._classes = classes
        n = dc_diff.shape[0]
        self._n = n
        out = _run_kernel(dc_diff, ac, tile_blocks, interpret, classes)
        (self._syms, self._amps, self._lens, self._total, dc_h, ac_h) = out
        with obs.d2h(dc_h, ac_h):
            dc_h, ac_h = jax.device_get((dc_h, ac_h))
        self.dc_freq = _hist(dc_h, classes)
        self.ac_freq = _hist(ac_h, classes)

    def payload(self, dc_table, ac_table) -> bytes:
        with obs.device_route("pack", self._interpret):
            return self._payload(dc_table, ac_table)

    def _payload(self, dc_table, ac_table) -> bytes:
        extra = []
        if self._classes == rle.ONE_CLASS:
            luts = (*huffman.encoder_luts(dc_table),
                    *huffman.encoder_luts(ac_table))
        else:
            luts = (*rle.class_luts(dc_table), *rle.class_luts(ac_table))
            extra = [rle.block_classes(self._classes, self._syms.shape[0])
                     .astype(np.int32).reshape(-1, 1)]
        luts = [np.asarray(a, np.int32) for a in (*luts, *extra)]
        with obs.h2d(*luts):
            luts = [jnp.asarray(a) for a in luts]
        f, w, s, total_bits, bad = _fields_device(
            self._syms, self._amps, self._lens, self._total, *luts)
        with obs.d2h(bad, total_bits):
            bad, total = jax.device_get((bad, total_bits))
        if bool(bad):
            raise ValueError("symbol stream contains a symbol absent "
                             "from the Huffman table")
        total = int(total)
        if total == 0:
            return b""
        tile_bits = tuning.tile_for("pack_bits", total)
        m = int(f.shape[0])
        pad = pack_ops.field_blocks(m, tile_bits) - m
        # padding starts sit at the payload end (zero width), so the
        # field ends stay sorted for the window search
        fields = jnp.stack([
            jnp.pad(f, (0, pad)), jnp.pad(w, (0, pad)),
            jnp.concatenate([s, jnp.broadcast_to(total_bits, (pad,))])
        ]).astype(jnp.int32)
        return pack_ops.pack_fields_device(fields, total, tile_bits,
                                           self._interpret)


def prepare(dc_diff, ac, packer=None, classes: tuple = rle.ONE_CLASS, *,
            backend: str = "auto", tile_blocks: int | None = None,
            interpret: bool | None = None):
    """Routed ``symbolizer=`` for the container encoders.

    Maps ``(dc_diff, ac, packer=None, classes=...)`` to a prepared
    stream with ``dc_freq`` / ``ac_freq`` histogram attributes and a
    ``payload(dc_table, ac_table) -> bytes`` method — the two-phase
    shape :func:`repro.core.entropy.container._frame_stream` needs for
    table negotiation.  Bytes are identical across backends and to the
    default (``symbolizer=None``) host route (CI-gated).

    On "pallas", a stream the device guards accept packs through the
    chained device scatter-pack; any other stream takes the host
    symbolizer (:func:`repro.core.entropy.dense.prepare`) and packs
    with ``packer``.  A ``classes=`` pattern with more than one table
    class gives (n_classes, 256) histograms and a ``payload`` that takes
    one table per class, on either route.
    """
    dc_diff = np.asarray(dc_diff, dtype=np.int64)
    ac = np.asarray(ac, dtype=np.int64)
    if select_backend(backend) == "numpy" or not _device_ok(dc_diff, ac):
        return dense.prepare(dc_diff, ac, packer, classes)
    from repro.kernels import common
    if interpret is None:
        interpret = common.interpret_default()
    if tile_blocks is None:
        tile_blocks = tuning.tile_for("symbolize", dc_diff.shape[0])
    with obs.device_route("symbolize", interpret, blocks=dc_diff.shape[0]):
        return _PallasPrepared(dc_diff, ac, tile_blocks, interpret, classes)


def make_symbolizer():
    """The encode's symbolize route, chosen from the platform.

    ``None`` off the TPU — the container encoders then keep their
    default, the host symbolizer — and :func:`prepare` on the Pallas
    route on a TPU, where its device guards still send oversized or
    out-of-range streams to the host.
    """
    if select_backend() == "numpy":
        return None
    return functools.partial(prepare, backend="pallas")
