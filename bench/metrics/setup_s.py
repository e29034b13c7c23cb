"""Seconds from process start to the start of the window: problem,
schedule, engine plans, initial state, compiles (or cache reads) and the
warm-up chunk."""


def read(ctx):
    return ctx.setup_s
