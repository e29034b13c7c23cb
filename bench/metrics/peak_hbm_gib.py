"""Highest ``peak_bytes_in_use`` over the cell's devices, read after the
window (before the check's reference runs), in GiB."""


def read(ctx):
    if not ctx.peaks:
        return None
    return max(ctx.peak_bytes) / 2 ** 30
