"""The fused roundtrip program's share of its roofline, in %.

Device time: every execution of the jitted ``_fused_roundtrip_sharded``
program in the window, all its operations (profiler trace). Least time:
the work of each call counted from its shape (``perfbench/work.py``)
against the chip's peaks (``perfbench/peaks.json``); bytes bound it.
"""

from perfbench import work

PROGRAM = r"_fused_roundtrip_sharded"


def read(ctx):
    device_s = ctx.trace.module_ns("roundtrip", PROGRAM) / 1e9
    if not device_s:
        return None
    peaks = ctx.peaks()
    least = 0.0
    for shape, calls in ctx.driver.work_calls():
        t, _ = work.least_seconds(*work.roundtrip_work(shape), peaks)
        least += calls * t
    return 100.0 * least / device_s
