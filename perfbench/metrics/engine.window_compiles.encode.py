"""Programs compiled or loaded from the compile cache while the window
was inside an encode call (JAX monitoring events)."""


def read(ctx):
    return float(ctx.phases.compiles.get("encode", 0))
