"""Host milliseconds per round inside the program's host-path spans
(int<->limb conversions and the L(x)*mu step; the waits for the device,
``host:fetch``, left out), from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, False, spans.host_path_s)
