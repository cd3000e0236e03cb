"""Samples of every step completed in the window, over the whole window
(host clock). In checkpointing cells the window includes the final flush."""


def read(run):
    steps = len(run.window.steps)
    return steps * run.batch / run.window.length if steps else None
