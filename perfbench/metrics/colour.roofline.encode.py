"""The colour compress program's share of its roofline, in %.

Device time: every execution of the jitted ``_compress_sharded_colour``
program inside encode calls (profiler trace): RGB -> YCbCr, the 2x2
chroma mean, the DCT and quantisation of three planes and the MCU
interleave with zig-zag. Least time: the work of the window's encoded
pixels (``perfbench/work_colour.py``) against the chip's peaks
(``perfbench/peaks.json``); bytes bound it.
"""

from perfbench import work_colour

PROGRAM = r"_compress_sharded_colour"


def read(ctx):
    device_s = ctx.trace.module_ns("encode", PROGRAM) / 1e9
    px = ctx.pixels.get("encode", 0)
    if not device_s or not px:
        return None
    t, _ = work_colour.least_seconds(*work_colour.encode_work(px),
                                     ctx.peaks())
    return 100.0 * t / device_s
