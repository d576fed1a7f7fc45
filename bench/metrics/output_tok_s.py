"""Output tokens delivered by the decode chunks that ended inside the
window, over the window's seconds."""
from bench.readers import output_tok_s


def read(run):
    return output_tok_s(run)
