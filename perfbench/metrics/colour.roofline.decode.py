"""The colour inverse program's share of its roofline, in %.

Device time: every execution of the jitted ``_decompress_sharded_colour``
program inside decode calls (profiler trace): dequantisation, the
inverse DCT of three planes, the h2v2 fancy chroma upsampling and
YCbCr -> RGB. Least time: the work of the window's decoded pixels
(``perfbench/work_colour.py``) against the chip's peaks
(``perfbench/peaks.json``); bytes bound it.
"""

from perfbench import work_colour

PROGRAM = r"_decompress_sharded_colour"


def read(ctx):
    device_s = ctx.trace.module_ns("decode", PROGRAM) / 1e9
    px = ctx.pixels.get("decode", 0)
    if not device_s or not px:
        return None
    t, _ = work_colour.least_seconds(*work_colour.decode_work(px),
                                     ctx.peaks())
    return 100.0 * t / device_s
