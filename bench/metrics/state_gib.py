"""Packed engine state per device, from shapes: ``(4n + 2 E_A + H (n +
E_A))`` float32 rows of the flat width, over the chips that shard it."""


def read(ctx):
    return ctx.counts["state_bytes_per_device"] / 2 ** 30
