"""Layer: entry points / compile. Backend compile requests
(``jax.monitoring``) between the first window dispatch and ``t1``. Must be
0: a compile inside the window is set-up charged to the rate."""


def read(ctx):
    return ctx["window"]["compiles_in_window"]
