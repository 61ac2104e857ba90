"""XLA backend compilations inside the measured window, counted from
``jax.monitoring``'s backend-compile events (a program read back from the
persistent cache counts too: the window waited for it)."""


def read(ctx):
    return ctx["compiles"]
