"""Share (%) of the decode chunks' slot-steps in the window in which a
slot was live: the engine's ``decode_live_steps`` over
``decode_slot_steps`` (every slot, every step of every chunk)."""


def read(run):
    a, b = run.rec.stats_open, run.rec.stats_close
    if "decode_slot_steps" not in b:
        return None
    steps = b["decode_slot_steps"] - a["decode_slot_steps"]
    live = b["decode_live_steps"] - a["decode_live_steps"]
    return 100.0 * live / steps if steps else None
