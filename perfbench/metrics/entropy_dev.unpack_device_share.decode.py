"""Share of the window's decoded colour streams whose entropy unpack ran
on the device, in %: the program's counters over the window,
``entropy.unpack.pallas`` (one per stream unpacked by the compiled
kernel) over ``engine.images.colour.decoded``. A program without those
counters reads nothing."""


def read(ctx):
    decoded = ctx.counters.get("engine.images.colour.decoded", 0)
    if not decoded:
        return None
    return 100.0 * ctx.counters.get("entropy.unpack.pallas", 0) / decoded
