"""Summed backend-compile seconds before the window (0 where every
program came from the persistent compilation cache)."""


def read(ctx):
    return sum(s for t, s in ctx.compiles if t < ctx.t_window[0])
