"""Device milliseconds per round of the executions enqueued under the
program's ``launch:dec`` spans (dec: the c^lam ladder), from the trace
of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, False, lambda red: red["busy_under"].get(
        "launch:dec", 0.0))
