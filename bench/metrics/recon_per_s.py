"""Reconciliations completed correctly per second of the window (host clock)."""


def read(run):
    return run.correct_recons / run.window_s
