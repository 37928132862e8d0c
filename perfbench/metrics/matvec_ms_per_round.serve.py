"""Device milliseconds per fleet round of the executions enqueued under the
engine's ``serve:launch:matvec`` spans (the homomorphic matvec: the per-
element ladder and its product tree), from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, True, lambda red: red["busy_under"].get(
        "serve:launch:matvec", 0.0))
