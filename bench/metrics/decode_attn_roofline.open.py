"""Share (%) of the roofline reached by the paged decode-attention kernel:
for every call in the window, the larger of its operations over the peak
rate and its bytes over the peak bandwidth (counted from the live
lengths), summed, over the kernel's device time in the trace."""
from bench.readers import DECODE_KERNEL, decode_work, roofline


def read(run):
    if run.trace is None:
        return None
    work, _ = decode_work(run)
    return roofline(work, run.trace.op_seconds(DECODE_KERNEL))
