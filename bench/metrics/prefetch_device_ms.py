"""Device time of the step program's relaxed prefetch (the next batch's
bag lookup on the pre-update table plus the correction gathered from the
update), its ``prefetch`` phase, in ms per step, read from the trace by
``bench/scopes.py``."""

PHASES = ("prefetch",)


def read(run):
    from bench import scopes
    scoped = scopes.of_run(run)
    return scoped.phase_ms(PHASES) if scoped else None
