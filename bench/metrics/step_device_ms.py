"""Device time of one execution of the step program (the XLA module named
after relaxed_step), mean over the traced window, in milliseconds."""

PROGRAM = "jit_relaxed_step"


def read(run):
    if run.trace is None:
        return None
    times = run.trace.module_times(PROGRAM)
    return 1e3 * sum(times) / len(times) if times else None
