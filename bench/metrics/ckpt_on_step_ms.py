"""Host time of CheckpointManager.on_step per window step, in ms, from the
benchmark's wrapper: the gather of touched rows, the dense device_get on
save steps, and any wait for room in the writer's queue."""


def read(run):
    t = [run.probe.on_step_s[n] for n in run.window.steps
         if n in run.probe.on_step_s]
    return 1e3 * sum(t) / len(t) if t else None
