"""JE self time per JE step: the host wall of ``ServingJobEngine.step`` less
the TEs' own ``step_wall``, over the traced stretch (host clock)."""


def read(ctx):
    c = ctx["counters"]
    if c["je_steps"] == 0:
        return None
    return (c["je_wall"] - c["te_wall"]) / c["je_steps"] * 1e3
