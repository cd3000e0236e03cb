"""Mean gap between consecutive tier-E commits of the window steps, in ms:
the writer thread's pace, dense (tier-M) saves included."""


def read(run):
    ts = sorted(run.probe.commit_t[n] for n in run.window.steps
                if n in run.probe.commit_t)
    if len(ts) < 2:
        return None
    return 1e3 * (ts[-1] - ts[0]) / (len(ts) - 1)
