"""Device time of the step program's dense tier, in ms per step: the
``bottom_mlp``, ``interaction`` and ``top_mlp`` phases, forward and
backward, and ``dense_update`` (clip, optimizer, apply), read from the
trace by ``bench/scopes.py``."""

PHASES = ("bottom_mlp", "interaction", "top_mlp", "dense_update")


def read(run):
    from bench import scopes
    scoped = scopes.of_run(run)
    return scoped.phase_ms(PHASES) if scoped else None
