"""80th percentile of time to first token over the requests due in the
window, from when each was due to when admit() returned its first token:
the highest percentile with ten or more of a window's ~50 requests beyond
it."""
from bench.readers import p80, ttft_ms


def read(run):
    return p80(ttft_ms(run))
