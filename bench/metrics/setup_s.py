"""Process start to the window's opening: fit check, weights, pool,
compile or cache load, warm-up, resident documents."""


def read(run):
    return run.setup_s
