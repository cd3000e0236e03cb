"""95th percentile of all per-step times in the window, in milliseconds.
A step's time runs between consecutive ``on_metrics`` calls of ``train()``
(each after the step's loss returned), on the host clock."""


def read(run):
    from bench.harness import percentile
    times = run.window.step_times()
    return 1e3 * percentile(times, 95) if times else None
