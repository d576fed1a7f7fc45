"""Host-clock milliseconds per decode step: the seconds of every decode
chunk of the window (as the engine's on_chunk measured them) over their
steps."""
from bench.readers import decode_step_ms


def read(run):
    return decode_step_ms(run)
