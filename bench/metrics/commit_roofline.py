"""Share of the HBM roofline the commit kernel (``commit_grid``'s Mosaic
launch) reaches, in %: the bytes its live lanes need per device
(``counters.commit_bytes``) over peak HBM bandwidth, divided by the
kernel's summed device time in the trace; averaged over the devices."""
KERNEL = r'custom_call_target="tpu_custom_call"'   # commit_grid's Mosaic call


def read(ctx):
    if ctx.trace is None or not ctx.peaks or not ctx.events:
        return None
    secs = ctx.trace.op_seconds(KERNEL)
    if not all(secs):
        return None
    need = ctx.counts["commit_bytes_per_event_per_device"] * ctx.events
    floor = need / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * sum(floor / s for s in secs) / len(secs)
