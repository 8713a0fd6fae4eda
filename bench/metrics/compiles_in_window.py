"""Backend compilations (or persistent-cache loads) inside the window,
counted by a ``jax.monitoring`` listener. Set-up warms every shape the
traffic uses, so anything above 0 is a compile the window paid for."""


def read(run):
    return float(run.compiles_in_window)
