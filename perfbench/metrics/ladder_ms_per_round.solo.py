"""Device milliseconds of the modular-exponentiation ladder programs per
round, from the trace of the window."""


def read(run):
    if run.engine or run.trace is None or not run.rounds:
        return None
    return 1e3 * run.trace["ladder_s"] / run.rounds
