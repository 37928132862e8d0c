"""Tenant rounds completed per host-clock second over the whole window."""


def read(run):
    if not run.engine or not run.rounds:
        return None
    return run.rounds / run.window_s
