"""Share (%) of the roofline reached by the paged prefill-attention kernel,
counted as for decode_attn_roofline from the rows of each admission wave."""
from bench.readers import PREFILL_KERNEL, prefill_work, roofline


def read(run):
    if run.trace is None:
        return None
    work, _ = prefill_work(run)
    return roofline(work, run.trace.op_seconds(PREFILL_KERNEL))
