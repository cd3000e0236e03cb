"""Seconds to recover the flushed checkpoint and have the resumed state on
the device (recovery.recover + recovery.resume_train_state)."""


def read(run):
    return run.resume_s
