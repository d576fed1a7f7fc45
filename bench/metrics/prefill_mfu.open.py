"""Share (%) of the chip's bf16 peak reached by the prefill program: the
model operations of the prompt tokens prefilled in the window over the
prefill program's device time in the trace, times the peak."""
from bench.readers import PREFILL_PROGRAM, mfu, prefill_work


def read(run):
    if run.trace is None:
        return None
    _, flops = prefill_work(run)
    return mfu(flops, run.trace.module_seconds(PREFILL_PROGRAM), run.peaks)
