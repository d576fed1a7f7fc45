"""80th percentile over the finished requests due in the window of
(completion - first token) / (output tokens - 1)."""
from bench.readers import p80, tpot_ms


def read(run):
    return p80(tpot_ms(run))
