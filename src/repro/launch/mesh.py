"""Production mesh definitions (MULTI-POD DRY-RUN spec).

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — device count is
locked on first jax init, and only launch/dryrun.py sets the 512-device
XLA flag.
"""

from __future__ import annotations

import jax


def _mk(shape: tuple, axes: tuple):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod stacks 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple, axes: tuple):
    """Arbitrary mesh for tests / reduced runs."""
    return _mk(shape, axes)


def make_data_mesh(n_devices: int | None = None):
    """1-D "data" mesh over the local devices (batch-sharded serving)."""
    n = n_devices if n_devices is not None else jax.local_device_count()
    return _mk((n,), ("data",))


# Per-chip peaks used by the roofline analysis, keyed by
# ``jax.Device.device_kind``.  Source: Google Cloud TPU documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip;
# 1,600 Gbit/s inter-chip interconnect, of which ``ici_bw`` keeps one
# conservative 50 GB/s link).
PEAKS = {
    "TPU v5 lite": dict(
        peak_flops_bf16=197e12,
        hbm_bw=819e9,
        ici_bw=50e9,
        hbm_bytes=16e9,
    ),
}


def chip_peaks(device=None) -> dict:
    """Peak terms of ``device`` (default: the first local device).

    Raises:
        KeyError: the device kind has no entry in :data:`PEAKS` — a
            roofline against another chip's peaks would be wrong, so
            there is no default.
    """
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind
    if kind not in PEAKS:
        raise KeyError(f"no peak terms for device kind {kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return dict(PEAKS[kind])
