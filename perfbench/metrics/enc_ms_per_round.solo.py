"""Device milliseconds per round of the executions enqueued under the
program's ``launch:enc`` spans (enc: the blinding ladder r^n and the
affine lift), from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, False, lambda red: red["busy_under"].get(
        "launch:enc", 0.0))
