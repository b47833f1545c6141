"""Device time of the decode programs (the fused horizon and, where the TE
fell back to it, the per-step decode) per decode step over the traced
stretch."""


def read(ctx):
    steps = ctx["counters"]["decode_steps"]
    prog = ctx["trace"]["program_s"]
    t = prog.get("fused_decode", 0.0) + prog.get("legacy_decode", 0.0)
    if steps <= 0 or t <= 0:
        return None
    return t / steps * 1e3
