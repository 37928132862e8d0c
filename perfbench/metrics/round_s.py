"""Host-clock seconds per protocol round of a single deployment: the
window's length over the rounds that ended in it."""


def read(run):
    if run.engine or not run.rounds:
        return None
    return run.window_s / run.rounds
