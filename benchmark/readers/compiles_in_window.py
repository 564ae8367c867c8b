"""Programs the backend compiled inside the window (jax.monitoring
backend compiles less persistent-cache hits); there should be none."""


def read(ctx):
    return ctx["compiles_in_window"]
