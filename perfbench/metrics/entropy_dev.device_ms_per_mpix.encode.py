"""Device time of the entropy programs inside encode calls, in ms per
megapixel encoded (profiler trace): every program of the encode phase
but the transform's, that is the symbolize and pack_bits kernels and the
XLA programs around them (codeword gather, window blocks, padding)."""

TRANSFORM = r"_compress_sharded|^jit_gather\("


def read(ctx):
    ns = ctx.trace.module_ns("encode", TRANSFORM, invert=True)
    mpx = ctx.pixels.get("encode", 0) / 1e6
    return ns / 1e6 / mpx if ns and mpx else None
