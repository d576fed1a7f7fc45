"""Share (%) of the traced window in which no operation ran on the chip."""
from bench.readers import device_idle


def read(run):
    return device_idle(run)
