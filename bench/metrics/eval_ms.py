"""Mean milliseconds of the harness's ``bench.eval`` span (the body of
``eval_fn``: the mean iterate, its placement, the loss, which ends in a
host sync) over the window's evals."""


def read(ctx):
    if not ctx.eval_s:
        return None
    return 1e3 * sum(ctx.eval_s) / len(ctx.eval_s)
