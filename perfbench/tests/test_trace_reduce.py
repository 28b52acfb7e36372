"""The trace reduction: interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on a TPU v5e."""

import pathlib

import pytest

from perfbench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"


def _trace():
    ops = {"/device:TPU:0": [("a", 10, 20), ("b", 15, 30), ("a", 50, 60),
                             ("c", 95, 120)]}
    mods = {"/device:TPU:0": [("jit_f", 10, 30), ("jit_g", 50, 60)]}
    phases = {"window": [(0, 100)], "encode": [(0, 40)],
              "decode": [(40, 100)]}
    return tr.Trace(ops, mods, phases)


def test_merge_and_intersect():
    assert tr.merge([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert tr.intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                             (20, 25)]


def test_busy_idle_and_modules():
    t = _trace()
    assert t.busy_ns("window") == 20 + 10 + 5
    assert t.busy_ns("encode") == 20
    assert t.idle_share("encode") == pytest.approx(0.5)
    assert t.idle_share("decode") == pytest.approx(1 - 15 / 60)
    assert t.idle_share("absent") is None
    assert t.module_ns("encode", "jit_f") == 20
    assert t.module_ns("decode", "jit_") == 10


def test_top_ops_and_idle_gaps():
    t = _trace()
    assert t.top_ops("window") == [["a", pytest.approx(20e-9)],
                                   ["b", pytest.approx(15e-9)],
                                   ["c", pytest.approx(5e-9)]]
    gaps = t.idle_gaps("window", ["encode", "decode"])
    assert gaps == [["decode", pytest.approx(35e-9)],
                    ["decode", pytest.approx(20e-9)],
                    ["encode", pytest.approx(10e-9)]]


def test_recorded_chip_trace():
    """A traced paper512.roundtrip window of one call, recorded on one
    TPU v5e (``run.py --seconds 0.05 --trace 1 --keep-trace``)."""
    t = tr.Trace.from_file(str(DATA / "paper512.roundtrip.xplane.pb"))
    assert t.devices == ["/device:TPU:0"]
    assert {"window", "roundtrip"} <= set(t.phases)
    assert 0 < t.busy_ns("roundtrip") <= t.wall_ns("roundtrip")
    assert 0 < t.idle_share("roundtrip") < 1
    fused = t.module_ns("roundtrip", r"_fused_roundtrip_sharded")
    assert 0 < fused <= t.busy_ns("roundtrip")
    names = [n for n, _ in t.top_ops("window")]
    assert any("fused_codec" in n for n in names)
    assert t.idle_gaps("window", ["roundtrip"])[0][0] in ("roundtrip",
                                                          "other")
