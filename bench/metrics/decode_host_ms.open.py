"""Host milliseconds per decode chunk in the window outside the wait for
the device: the engine's ``decode_prep_seconds`` (inputs to the device),
``decode_dispatch_seconds`` (the chunk call) and ``decode_post_seconds``
(tokens to requests, retirement), over ``decode_chunks``."""

KEYS = ("decode_prep_seconds", "decode_dispatch_seconds",
        "decode_post_seconds")


def read(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    if "decode_chunks" not in b:
        return None
    chunks = b["decode_chunks"] - a["decode_chunks"]
    host = sum(b[k] - a[k] for k in KEYS)
    return host * 1e3 / chunks if chunks else None
