"""The program's spans against the device: interval arithmetic on
made-up events, and the span report of small cells traced on the CPU."""

import pytest

from perfbench import span_report
from perfbench import spans as sp
from perfbench import trace_reduce as tr
from perfbench.tests.small import small_cell

MAIN, POOL = ("/host:CPU", 0), ("/host:CPU", 1)


def _trace(spans=None):
    """Device busy 10-20 and 60-70 of an encode phase 0-100. The main
    line is in an engine call 0-100 and codes one image 5-45 with a copy
    15-25 inside it; a pool line codes another image 30-80."""
    ops = {"/device:TPU:0": [("k", 10, 20), ("k", 60, 70)]}
    if spans is None:
        spans = [
            (MAIN, "repro.engine.encode", 0, 100, {"call": 1, "images": 2}),
            (MAIN, "repro.entropy.encode_image", 5, 45,
             {"call": 1, "image": 0}),
            (MAIN, "repro.xfer.d2h", 15, 25, {"nbytes": 100}),
            (POOL, "repro.entropy.encode_image", 30, 80,
             {"call": 1, "image": 1}),
            (POOL, "repro.entropy.payload", 40, 50, {}),
            (POOL, "repro.xfer.h2d", 55, 58, {"nbytes": 28}),
            (MAIN, "repro.xfer.h2d", 150, 160, {"nbytes": 999}),
        ]
    trace = tr.Trace(ops, {}, {"encode": [(0, 100)], "decode": [(100, 200)]})
    return sp.SpanTrace(trace, spans)


def test_self_time_excludes_the_waits_and_sums_lines():
    t = _trace()
    # main: 5-45 less 15-25 = 30; pool: 30-80 less 55-58 = 47
    assert t.self_ns("encode", "repro.entropy.") == 30 + 47
    assert t.self_ns("encode", "repro.entropy.", exclude=()) == 40 + 50
    assert t.self_ns("decode", "repro.entropy.") == 0
    # main 0-100 less the image span 5-45
    assert t.self_ns("encode", "repro.engine.",
                     exclude=("repro.entropy.", "repro.xfer.")) == 60


def test_idle_under_unions_lines_and_leaves_busy_time_out():
    t = _trace()
    # self time, merged over lines: 5-15, 25-55, 58-80; less busy 10-20
    # and 60-70: 5-10, 25-55, 58-60, 70-80
    assert t.idle_under_ns("encode", "repro.entropy.") == 5 + 30 + 2 + 10
    no_device = sp.SpanTrace(tr.Trace({}, {}, {"encode": [(0, 100)]}),
                             t.spans)
    assert no_device.idle_under_ns("encode", "repro.") == 0.0


def test_stats_and_counts_by_phase():
    t = _trace()
    assert t.stat_sum("encode", "repro.xfer.", "nbytes") == 128
    assert t.stat_sum("decode", "repro.xfer.", "nbytes") == 999
    assert t.count("encode", "repro.entropy.encode_image") == 2
    assert t.count("decode", "repro.engine.") == 0


def test_idle_by_span_gives_each_instant_to_the_innermost_span():
    got = _trace().idle_by_span("encode")
    # [self, idle under it, its share of the idle time]: the device is
    # busy 10-20 and 60-70; where two lines are in spans, each takes half;
    # main line 0-5 and 45-100; the pool line is in a span 30-80
    assert got["repro.engine.encode"] == [5 + 55, 5 + 45, 5 + 12.5 + 20]
    # main 5-45 less 15-25; pool 30-80 less 40-50 and 55-58
    assert got["repro.entropy.encode_image"] == [
        30 + 37, 5 + 20 + 5 + 2 + 10, 5 + 5 + 10 + 2.5 + 2.5 + 1 + 5]
    assert got["repro.xfer.d2h"] == [10, 5, 5]
    assert got["repro.entropy.payload"] == [10, 10, 5]
    assert got["repro.xfer.h2d"] == [3, 3, 1.5]
    assert sum(v[2] for v in got.values()) == 100 - 20


def test_subtract():
    assert sp.subtract([(0, 10), (20, 30)], [(5, 8), (9, 22), (25, 26)]) \
        == [(0, 5), (8, 9), (22, 25), (26, 30)]
    assert sp.subtract([(0, 10)], []) == [(0, 10)]


def test_a_program_without_spans_reads_as_empty():
    t = _trace(spans=[])
    assert t.idle_by_span("encode") == {}
    driver = type("D", (), {"phases_measured": ("encode",),
                            "pixels": {"encode": 10**6}, "n_images": 4})()
    rep = span_report.report(t, driver)["encode"]
    assert rep["spans"] == {}
    assert set(rep["readings"].values()) == {None}


@pytest.mark.parametrize("name, phases", [
    ("thumb256.codec", ("encode", "decode")),
    ("paper512.roundtrip", ("roundtrip",))])
def test_span_report_of_a_small_cell(name, phases):
    st, driver = span_report.traced_window(small_cell(name), seed=5,
                                           seconds=0.5)
    rep = span_report.report(st, driver)
    assert tuple(rep) == phases
    for phase in phases:
        assert rep[phase]["idle_s"] is None      # a CPU trace: no device
        assert rep[phase]["spans"][f"repro.engine.{phase}"]["count"] > 0
    if name == "thumb256.codec":
        enc = rep["encode"]
        assert (enc["spans"]["repro.entropy.encode_image"]["count"]
                == driver.n_images)
        for phase in phases:
            got = rep[phase]["readings"]
            assert got["entropy_host_ms_per_mpix"] > 0
            # the CPU's host routes copy the levels (encode) and the
            # zig-zag streams (decode), 4 bytes per coefficient
            assert got["xfer_bytes_per_image"] >= 64 * 64 * 4
    else:
        assert rep["roundtrip"]["readings"]["reassemble_ms_per_call"] > 0
