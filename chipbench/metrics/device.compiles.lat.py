"""Backend compiles inside the measured window and its drain, counted from
``jax.monitoring`` events."""


def read(run):
    return run.compiles
