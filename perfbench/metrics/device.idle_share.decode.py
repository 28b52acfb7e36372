"""Share of the decode phase's wall time in which the device ran no
operation, in %, from the profiler trace."""


def read(ctx):
    share = ctx.trace.idle_share("decode")
    return None if share is None else 100.0 * share
