"""Sequences per decode step of the TE scheduler: the decode batch as it
stood before each step, weighted by the decode steps that step ran."""


def read(ctx):
    c = ctx["counters"]
    if c["decode_steps"] <= 0 or c["decode_rows"] <= 0:
        return None
    return c["decode_rows"] / c["decode_steps"]
