"""Milliseconds each request admitted in the window waited in the
engine's queue, from ``enqueue`` to the start of the admission wave that
took it: the engine's ``queue_wait_seconds`` over ``admitted``."""


def read(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    if "queue_wait_seconds" not in b:
        return None
    n = b["admitted"] - a["admitted"]
    wait = b["queue_wait_seconds"] - a["queue_wait_seconds"]
    return wait * 1e3 / n if n else None
