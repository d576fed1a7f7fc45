"""Share (%) of the chip's bf16 peak reached by the decode program: the
model operations of every live slot's decode steps in the window (matmuls,
lm head, attention over the live lengths) over the decode program's device
time in the trace, times the peak."""
from bench.readers import DECODE_PROGRAM, decode_work, mfu


def read(run):
    if run.trace is None:
        return None
    _, flops = decode_work(run)
    return mfu(flops, run.trace.module_seconds(DECODE_PROGRAM), run.peaks)
