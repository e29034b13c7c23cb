"""The commit kernel's device milliseconds per event in the traced window,
averaged over the devices."""
KERNEL = r'custom_call_target="tpu_custom_call"'   # commit_grid's Mosaic call


def read(ctx):
    if ctx.trace is None or not ctx.events:
        return None
    secs = ctx.trace.op_seconds(KERNEL)
    if not all(secs):
        return None
    return 1e3 * sum(secs) / len(secs) / ctx.events
