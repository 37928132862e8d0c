"""Host milliseconds per fleet round inside the program's host-path spans
(int<->limb conversions, row packing and unpacking, the L(x)*mu step; the
waits for the device, ``host:fetch``, left out), from the trace of the
window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, True, spans.host_path_s)
