"""1 - (union of the device's operation intervals / traced window),
averaged over the cell's devices, from the profiler trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace.idle_frac()
