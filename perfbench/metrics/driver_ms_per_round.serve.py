"""Host milliseconds per fleet round inside the protocol driver's spans
of every tenant (quantize, dequantize, global update) where no inner
program span is open, from the trace of the window."""
from perfbench import spans


def read(run):
    return spans.per_round(run, True, spans.driver_s)
