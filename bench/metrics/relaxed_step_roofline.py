"""Share of its roofline the step program reaches: the least time the
step's required work needs at the chip's peaks (the configuration's counts
module, bench/counts.py by default), over the program's device time, mean
over the traced window's steps, in %."""

PROGRAM = "jit_relaxed_step"


def read(run):
    from bench import feed
    if run.trace is None or not run.window.steps:
        return None
    times = run.trace.module_times(PROGRAM)
    if not times:
        return None
    least = [run.spec.counts.step_min_seconds(
        run.sizes, feed.unique_rows(run.sizes, run.ring[n % len(run.ring)]),
        run.peaks)[0] for n in run.window.steps]
    return 100.0 * (sum(least) / len(least)) / (sum(times) / len(times))
