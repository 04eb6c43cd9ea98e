"""Backend compiles (persistent-cache loads included) inside the window,
counted by a ``jax.monitoring`` listener: eager operations too, not only the
broker's cohort executables. Warm-up should leave none."""


def read(run):
    return run.compiles_in_window
