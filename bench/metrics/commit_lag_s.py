"""Mean over the window steps of (time the step was committed to the
checkpoint - time its loss returned): the work a crash would lose."""


def read(run):
    p = run.probe
    lags = [p.commit_t[n] - p.loss_t[n] for n in run.window.steps
            if n in p.commit_t and n in p.loss_t]
    if not lags or len(lags) < len(run.window.steps):
        return None
    return sum(lags) / len(lags)
