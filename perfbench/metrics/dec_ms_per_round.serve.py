"""Device milliseconds per fleet round of the executions enqueued under the
engine's ``serve:launch:dec`` spans (dec: the c^lam ladder), from the
trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, True, lambda red: red["busy_under"].get(
        "serve:launch:dec", 0.0))
