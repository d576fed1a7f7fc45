"""Host milliseconds per admission wave in the window before the wave
waits on the device: the engine's ``admit_plan_seconds`` (queue loop,
prefix lookup, pages) plus ``admit_dispatch_seconds`` (copy-on-write and
prefill chunk dispatches), over ``admit_waves``."""

KEYS = ("admit_plan_seconds", "admit_dispatch_seconds")


def read(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    if "admit_waves" not in b:
        return None
    waves = b["admit_waves"] - a["admit_waves"]
    host = sum(b[k] - a[k] for k in KEYS)
    return host * 1e3 / waves if waves else None
