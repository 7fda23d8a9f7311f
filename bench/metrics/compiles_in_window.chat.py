"""Backend compiles (or cache fetches) inside the measured window, counted
by a ``jax.monitoring`` listener; every shape should have been warmed up,
so this reads 0."""


def read(run):
    return float(run.window.compiles)
