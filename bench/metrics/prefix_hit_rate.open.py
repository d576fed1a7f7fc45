"""Share (%) of the prompt tokens admitted in the window that the prefix
cache served: cached / (cached + prefilled), from the engine's counters."""
from bench.readers import prefix_hit_rate


def read(run):
    return prefix_hit_rate(run)
