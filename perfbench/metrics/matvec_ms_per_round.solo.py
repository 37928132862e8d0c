"""Device milliseconds per round of the executions enqueued under the
program's ``launch:matvec`` spans (the homomorphic matvec: the per-
element ladder and its product tree), from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, False, lambda red: red["busy_under"].get(
        "launch:matvec", 0.0))
