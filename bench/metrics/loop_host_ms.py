"""Host time of the training loop per step, in ms, from the program's own
spans: the mean over the iterations inside the traced window of the
``repro.train.step`` span less its ``repro.train.loss_read`` (the blocking
read of the loss). The loop is serial, so this is the host work it puts
between two device steps: batch hand-off, dispatch, ``on_step``,
``on_metrics``."""


def read(run):
    from bench import scopes
    scoped = scopes.of_run(run)
    return scoped.loop_host_ms() if scoped else None
