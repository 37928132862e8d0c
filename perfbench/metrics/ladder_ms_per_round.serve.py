"""Device milliseconds of the rows ladder programs per fleet round, from
the trace of the window."""


def read(run):
    if not run.engine or run.trace is None or not run.rounds:
        return None
    return 1e3 * run.trace["ladder_s"] / run.engine_rounds
