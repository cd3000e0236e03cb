"""Device time of the step program's sparse update, in ms per step: its
``embed_grad`` (the lookup's adjoint into a table-shaped gradient) and
``embed_update`` (embedding optimizer, pool layout, apply) phases, read
from the trace by ``bench/scopes.py``."""

PHASES = ("embed_grad", "embed_update")


def read(run):
    from bench import scopes
    scoped = scopes.of_run(run)
    return scoped.phase_ms(PHASES) if scoped else None
