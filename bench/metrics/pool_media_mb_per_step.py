"""Bytes the pool counts on its media per window step, in MB (10^6 B):
PoolMetrics' media byte counters only, never its modeled times."""


def read(run):
    steps = len(run.window.steps)
    if not steps or not run.pool_bytes_in_window:
        return None
    return run.pool_bytes_in_window / steps / 1e6
