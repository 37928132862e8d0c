"""Host milliseconds per round inside the protocol driver's spans
(quantize, dequantize, global update) where no inner program span is
open, from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, False, spans.driver_s)
