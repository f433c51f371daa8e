"""JAX backend compiles inside the window, persistent-cache loads
included, counted from JAX's own monitoring events (should be 0)."""


def read(run):
    return run.window_compiles
