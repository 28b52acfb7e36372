"""Share of the window's decoded colour streams whose block chain was
resolved, and whose coefficients were emitted, on the device, in %: the
program's counters over the window, ``entropy.resolve.device`` (one per
stream resolved by the device program) over
``engine.images.colour.decoded``. A program that counts no resolve route
reads nothing."""


def read(ctx):
    decoded = ctx.counters.get("engine.images.colour.decoded", 0)
    routes = [ctx.counters.get(f"entropy.resolve.{how}")
              for how in ("device", "host")]
    if not decoded or routes == [None, None]:
        return None
    return 100.0 * (routes[0] or 0) / decoded
