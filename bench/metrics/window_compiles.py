"""Backend compiles (JAX's ``backend_compile_duration`` events) that fired
inside the window; the harness warms every shape first, so this reads 0."""


def read(ctx):
    t0, t1 = ctx.t_window
    return sum(1 for t, _ in ctx.compiles if t0 <= t <= t1)
