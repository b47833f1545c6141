"""Decode steps per decode dispatch over the traced stretch: how long the
fused horizons the TE scheduler could prove were (1 wherever a prompt is
queued), from the program's ``decode_steps`` / ``decode_dispatches``."""


def read(ctx):
    c = ctx["counters"]
    if c.get("decode_dispatches", 0) <= 0:
        return None
    return c["decode_steps"] / c["decode_dispatches"]
