"""Backend compiles (persistent-cache loads included) inside the window,
per window step, counted by the benchmark's own jax.monitoring listener."""


def read(run):
    steps = len(run.window.steps)
    return run.compiles_in_window / steps if steps else None
