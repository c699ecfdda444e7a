"""JAX compile events inside the measured window: jaxpr traces,
lowerings and backend compiles or persistent-cache loads. Every program is
warmed in set-up, so it should read 0."""


def read(run):
    return run.window_compiles
