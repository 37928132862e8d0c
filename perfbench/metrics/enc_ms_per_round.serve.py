"""Device milliseconds per fleet round of the executions enqueued under the
engine's ``serve:launch:enc`` spans (enc: the blinding ladder r^n and
the affine lift), from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, True, lambda red: red["busy_under"].get(
        "serve:launch:enc", 0.0))
