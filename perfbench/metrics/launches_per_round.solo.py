"""Coalescer launches per round of a single deployment (the runner's
``CoalesceQueue.launches`` over the window)."""


def read(run):
    if run.engine or not run.rounds:
        return None
    return run.launches / run.rounds
