"""Device time of the entropy programs inside decode calls, in ms per
megapixel decoded (profiler trace): the unpack_bits unit-word kernel and
its tile-staging program."""

ENTROPY = r"unit_words|stage_tiles"


def read(ctx):
    ns = ctx.trace.module_ns("decode", ENTROPY)
    mpx = ctx.pixels.get("decode", 0) / 1e6
    return ns / 1e6 / mpx if ns and mpx else None
