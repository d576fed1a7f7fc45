"""Share (%) of the window's wall time spent inside admit()."""
from bench.readers import admit_share


def read(run):
    return admit_share(run)
