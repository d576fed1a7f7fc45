"""Share (%) of the prefill rows dispatched in the window that held a
prompt token: the engine's ``prefill_tokens`` over
``prefill_slot_tokens`` (bucket rows x chunk width, per chunk step)."""


def read(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    if "prefill_slot_tokens" not in b:
        return None
    rows = b["prefill_slot_tokens"] - a["prefill_slot_tokens"]
    toks = b["prefill_tokens"] - a["prefill_tokens"]
    return 100.0 * toks / rows if rows else None
