"""Device time of the transform programs inside encode calls, in ms per
megapixel encoded (profiler trace): the staged DCT + quantise program
(``_compress_sharded``) and the zig-zag gather."""

TRANSFORM = r"_compress_sharded|^jit_gather\("


def read(ctx):
    ns = ctx.trace.module_ns("encode", TRANSFORM)
    mpx = ctx.pixels.get("encode", 0) / 1e6
    return ns / 1e6 / mpx if ns and mpx else None
