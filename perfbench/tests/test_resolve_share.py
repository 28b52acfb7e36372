"""The resolve-route share reads the program's counters over the window,
and reads nothing where the program counts no resolve route."""

import importlib.util
import pathlib
import types

import pytest

PATH = (pathlib.Path(__file__).resolve().parents[1] / "metrics"
        / "entropy_dev.resolve_device_share.decode.py")


def _read(counters):
    spec = importlib.util.spec_from_file_location("resolve_share", PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(types.SimpleNamespace(counters=counters))


@pytest.mark.parametrize("counters,want", [
    ({"engine.images.colour.decoded": 8, "entropy.resolve.device": 8},
     100.0),
    ({"engine.images.colour.decoded": 8, "entropy.resolve.device": 6,
      "entropy.resolve.host": 2}, 75.0),
    ({"engine.images.colour.decoded": 8, "entropy.resolve.host": 8}, 0.0),
    # a program without the counter (before the device resolver)
    ({"engine.images.colour.decoded": 8, "entropy.unpack.pallas": 8}, None),
    ({"entropy.resolve.device": 8}, None),     # no colour stream decoded
])
def test_reads_share_or_nothing(counters, want):
    assert _read(counters) == want
