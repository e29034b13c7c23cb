"""Training tokens whose gradients were committed in the window (events x
batch x seq), over the whole window: evals and host gaps count in the
time."""


def read(ctx):
    if ctx.window_s <= 0 or not ctx.events:
        return None
    return ctx.tokens / ctx.window_s
